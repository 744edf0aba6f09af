from torch_actor_critic_tpu.parallel.mesh import (  # noqa: F401
    global_device_put,
    local_dp_info,
    make_mesh,
)
from torch_actor_critic_tpu.parallel.dp import (  # noqa: F401
    DataParallelSAC,
    init_sharded_buffer,
    shard_chunk,
    shard_chunk_from_local,
)
from torch_actor_critic_tpu.parallel.distributed import (  # noqa: F401
    global_statistics,
    initialize_multihost,
    is_coordinator,
)
from torch_actor_critic_tpu.parallel.context import (  # noqa: F401
    context_parallel_actor_step,
    make_ring_attention_fn,
    ring_attention,
)
from torch_actor_critic_tpu.parallel.population import (  # noqa: F401
    PopulationLearner,
)
from torch_actor_critic_tpu.parallel.sharding import (  # noqa: F401
    fsdp_spec,
    param_specs,
    shard_params,
    tp_specs,
)
