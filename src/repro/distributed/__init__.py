from repro.distributed.serving import (SHARD_AXIS, serving_mesh,
                                       shard_devices)
from repro.distributed.sharding import (batch_shardings, cache_shardings,
                                        dp_axes, opt_shardings,
                                        param_shardings)

__all__ = ["SHARD_AXIS", "batch_shardings",
           "cache_shardings", "dp_axes", "opt_shardings", "param_shardings",
           "serving_mesh", "shard_devices"]
