from repro.distributed.sharding import (batch_axes, batch_spec, cache_specs,
                                        local_shape, named_shardings,
                                        param_specs, replication_factor,
                                        sanitize_spec, spec_shard_count)
from repro.distributed.compression import (ErrorFeedbackInt8,
                                           compressed_all_reduce,
                                           compressed_psum)
from repro.distributed.ctx import shard_map_unchecked

__all__ = ['batch_axes', 'batch_spec', 'cache_specs', 'local_shape',
           'named_shardings', 'param_specs', 'replication_factor',
           'sanitize_spec', 'spec_shard_count', 'ErrorFeedbackInt8',
           'compressed_all_reduce', 'compressed_psum', 'shard_map_unchecked']
