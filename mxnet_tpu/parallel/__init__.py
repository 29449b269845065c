"""TPU-native parallelism: meshes, sharded training, ring attention.

This package is the TPU-idiomatic replacement for the reference's entire
distributed stack (SURVEY.md §2.3/§2.4: DataParallelExecutorGroup slicing,
kvstore local/device/tree reducers, NCCL, ps-lite PS, Horovod, P3):
instead of replicating executors and pushing gradients through a store,
ONE jitted SPMD program runs over a ``jax.sharding.Mesh`` and XLA inserts
the collectives (psum/all-gather/reduce-scatter) over ICI/DCN.

Axes convention (How-to-Scale-Your-Model recipe):
  dp — data parallel (batch dim)     tp — tensor parallel (weight shards)
  pp — pipeline stages               sp — sequence/context parallel
  ep — expert parallel
"""
from .mesh import (make_mesh, local_mesh, data_parallel_spec,  # noqa: F401
                   mesh_shard_info, parse_mesh, llm_mesh)  # noqa: F401
from .functional import functional_call, extract_params, load_params  # noqa: F401
from .trainer import ShardedTrainer, shard_batch  # noqa: F401
from .ring_attention import ring_attention, sequence_shard  # noqa: F401
from .pipeline import (pipeline_stage_loop,  # noqa: F401
                       pipeline_value_and_grad,  # noqa: F401
                       hetero_pipeline, HeteroPipeline)  # noqa: F401
from .stages import gluon_pipeline_stages  # noqa: F401
from .auto_spec import auto_spec  # noqa: F401
