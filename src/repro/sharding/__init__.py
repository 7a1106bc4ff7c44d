from repro.sharding.rules import (shard, param_specs, scatter_dim, DATA_AXIS,
                                  MODEL_AXIS, POD_AXIS)

__all__ = ["shard", "param_specs", "scatter_dim", "DATA_AXIS", "MODEL_AXIS",
           "POD_AXIS"]
