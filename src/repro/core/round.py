"""The end of an averaging round, shared by every backend.

The vmapped simulator (``core.simulate``), its divergence-triggered
variant and the pjit round (``core.local_sgd.build_sync_step``) each
derive their own rng and carry their own state; what follows the
topology's reduce is the same in all three and lives here.
"""
import jax

from repro.utils.tree import tree_broadcast_leading, tree_mean_leading


def end_round(consensus, momentum, n: int):
    """``(params, momentum)`` for ``n`` clients after a round: the
    consensus (no client axis) broadcast back to every client, and the
    clients' stacked momentum averaged and broadcast. The mean runs under
    the named scope ``reduce`` and the broadcasts under ``broadcast``, so
    a profile's op metadata puts them in the round's two halves."""
    with jax.named_scope("reduce"):
        momentum = tree_mean_leading(momentum)
    with jax.named_scope("broadcast"):
        return (tree_broadcast_leading(consensus, n),
                tree_broadcast_leading(momentum, n))
