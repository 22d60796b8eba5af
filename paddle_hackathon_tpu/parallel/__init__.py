"""Distributed / parallel execution.

TPU-native replacement for the reference's entire distributed stack
(SURVEY §2.4, §5.8): ``jax.sharding.Mesh`` with named axes plays the role of
``CommunicateTopology``'s 4-D cartesian rank mesh (``topology.py:52``);
pjit/GSPMD sharding propagation replaces the fleet meta-optimizers' program
rewrites; explicit ``shard_map`` collectives replace the ``c_*`` comm ops;
``jax.distributed.initialize`` replaces TCPStore rendezvous.

Axis naming convention (matches fleet's ``[data, pipe, sharding, model]``
plus the new sequence axis):
  - ``dp``  data parallel (batch)
  - ``pp``  pipeline stages
  - ``sharding``  ZeRO parameter/grad/optimizer-state sharding
  - ``mp``  tensor (model) parallel
  - ``sp``  sequence/context parallel (ring attention / Ulysses)
"""

from .api import (create_mesh, get_mesh, make_sharded_train_step,  # noqa: F401
                  set_mesh, shard_params)
from .env import (get_rank, get_world_size, init_parallel_env,  # noqa: F401
                  is_initialized)
from . import collective  # noqa: F401
from .collective import (Group, ReduceOp, all_gather, all_reduce,  # noqa: F401
                         alltoall, barrier, broadcast, new_group, ppermute,
                         reduce, reduce_scatter, scatter, shift)
from .topology import (CommunicateTopology, HybridCommunicateGroup,  # noqa: F401
                       ParallelMode, get_hybrid_communicate_group,
                       init_hybrid_parallel, set_hybrid_communicate_group)
from .mp_layers import (ColumnParallelLinear, ParallelCrossEntropy,  # noqa: F401
                        RowParallelLinear, VocabParallelEmbedding,
                        mark_sharding, sharding_rule_from_model)
from .pipeline import (LayerDesc, PipelineLayer,  # noqa: F401
                       PipelineParallel, SharedLayerDesc,
                       pipeline_apply, pipeline_decode_apply,
                       stack_layer_params, unstack_into_layers)
from .sequence import (disable_sequence_parallel,  # noqa: F401
                       enable_sequence_parallel, ring_attention,
                       ulysses_attention)
from .moe import (DroplessMoELayer, GShardGate, MoELayer,  # noqa: F401
                  NaiveGate, SwitchGate, moe_active_params, moe_all_to_all)
from .multislice import (create_multislice_mesh,  # noqa: F401
                         dcn_traffic_axes)
from .sharding import (ZeroShardInfo,  # noqa: F401
                       group_sharded_parallel, save_group_sharded_model,
                       state_bytes, zero_data_axis)
from .fleet import (DistributedStrategy, distributed_model,  # noqa: F401
                    distributed_optimizer, fleet)
from .recompute import (jit_recompute, recompute,  # noqa: F401
                        recompute_sequential)
from .strategies import (DGCMomentumOptimizer,  # noqa: F401
                         FP16AllReduceOptimizer, GradientMergeOptimizer,
                         LocalSGDOptimizer)
from . import auto_parallel  # noqa: F401
from .auto_parallel import (Engine, ProcessMesh, shard_op,  # noqa: F401
                            shard_tensor)
from .store import TCPStore  # noqa: F401
from . import checkpointing  # noqa: F401
from .checkpointing import (CheckpointConfig, CheckpointManager,  # noqa: F401
                            CorruptCheckpointError, elastic_rendezvous)
from .dist_checkpoint import (load_sharded, load_train_state,  # noqa: F401
                              reshard, save_sharded, save_train_state)
from .planner import (MeshPlan, enumerate_meshes, plan_mesh,  # noqa: F401
                      plan_sharding, score_plan)
