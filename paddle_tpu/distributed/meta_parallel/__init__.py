"""Hybrid-parallel (dygraph "meta_parallel") stack — TPU-native.

Mirrors `python/paddle/distributed/fleet/meta_parallel/` of the reference:
tensor parallel layers (`parallel_layers/mp_layers.py`), pipeline layers +
schedule (`parallel_layers/pp_layers.py`, `pipeline_parallel.py`), sharding
(`sharding/`), and the model wrappers dispatched by
`fleet.distributed_model` (`fleet_base.py:836`).

Design: the reference implements each strategy with explicit NCCL
collectives (identity-fwd/allreduce-bwd ops, send_v2/recv_v2 P2P). Here the
primary mechanism is GSPMD: layers annotate weights/activations with
`PartitionSpec`s over the global mesh and XLA inserts the matching
collectives over ICI. Pipeline parallelism — which GSPMD does not express —
uses `jax.shard_map` over the 'pipe' axis with `lax.ppermute` microbatch
shifting (see pipeline_parallel.py).
"""
from .mp_layers import (  # noqa: F401
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from .pp_layers import LayerDesc, PipelineLayer, SharedLayerDesc  # noqa: F401
from .pipeline_parallel import PipelineParallel  # noqa: F401
from .tensor_parallel import (  # noqa: F401
    ShardingParallel,
    TensorParallel,
    shard_parameters,
)
from .sharding_optimizer import DygraphShardingOptimizer  # noqa: F401
from .sequence_parallel import (  # noqa: F401
    make_sp_attention,
    ring_attention,
    ulysses_attention,
)
from .stacked_pipeline import (  # noqa: F401
    gpipe,
    pipelined_apply,
    stack_stage_params,
    unstack_stage_params,
)
from ...framework.random import (  # noqa: F401
    RNGStatesTracker,
    get_rng_state_tracker,
    model_parallel_random_seed,
)
from .moe import MoEMLP, topk_gating  # noqa: F401
