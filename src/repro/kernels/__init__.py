# Pallas TPU kernels for the compute hot-spots of the models the paper's
# algorithm trains/serves (the paper's own contribution is a communication
# schedule — kernel-free — so kernels/ serves the substrate):
#   flash_attention/  blockwise online-softmax attention (causal/window/softcap/GQA);
#                     causal.py routes MLA's training attention to JAX's own
#                     splash kernel on TPU (the only one a model path calls)
#   fused_update/     fused momentum-SGD update (Local SGD's k-per-round inner loop)
#   quantize/         fused stochastic-round quantize + dequant-accumulate
#                     (the compressed communication round, repro.comm)
#   ssd/              Mamba2 SSD chunked scan in matmul-dual (MXU) form
#   replica_mean.py   the one-chip round: co-located client replicas averaged
#                     in one in-place pass (core.local_sgd on TPU)
# Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (public
# jit-able wrapper), ref.py (pure-jnp oracle used by the allclose tests).
