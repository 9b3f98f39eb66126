"""Parallelism: device meshes, data-parallel training, parallel inference.

TPU-native replacement for the reference's entire scaleout stack
(SURVEY.md §2.5): ParallelWrapper's averaging/gradient-sharing modes, both
Spark TrainingMasters, and the Aeron VoidParameterServer all collapse into
ONE mechanism — a jitted train step whose batch is sharded over a mesh axis
and whose gradients are all-reduced by XLA collectives over ICI (DCN across
slices). Threshold compression (EncodedGradientsAccumulator) and the
cross-replica sharded weight update are available as an OPT-IN explicit
exchange (parallel/grads.py, env DL4J_TPU_GRAD_COMPRESS /
DL4J_TPU_SHARDED_UPDATE): on a single ICI-connected slice the implicit dense
all-reduce is already optimal (SURVEY.md §5.8), but when the exchange
crosses DCN — multi-slice or Ethernet-attached hosts — the 16x ternary wire
format and the 1/R-per-replica optimizer math pay for themselves. Both
switches default OFF.
"""

from deeplearning4j_tpu.parallel.mesh import (
    MeshSpec, data_axis_size, data_sharded, make_mesh,
)
from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.parallel.context import current_mesh, use_mesh
from deeplearning4j_tpu.parallel.distributed import (
    global_array,
    init_distributed,
    is_multihost,
    replicate_global,
    shutdown_distributed,
)
from deeplearning4j_tpu.parallel.compress import (
    decode_gathered,
    encode_packed,
    pack_ternary,
    packed_nbytes,
    threshold_decode,
    threshold_encode,
    unpack_ternary,
)
from deeplearning4j_tpu.parallel.grads import DataParallelStep, GradExchange
from deeplearning4j_tpu.parallel.elastic import (
    ElasticRuntime,
    FileStore,
    Membership,
    MembershipChanged,
    View,
)
from deeplearning4j_tpu.parallel.gpipe import GPipeTrainer
from deeplearning4j_tpu.parallel.ring import local_attention, ring_self_attention
from deeplearning4j_tpu.parallel.pipeline import PipelineParallel, stack_stage_params
from deeplearning4j_tpu.parallel.tp import ShardedTrainer, tp_param_shardings
from deeplearning4j_tpu.parallel.mesh_step import MeshTrainer, shard_update_spec

__all__ = [
    "MeshSpec", "make_mesh", "ParallelWrapper", "ParallelInference",
    "current_mesh", "use_mesh", "local_attention", "ring_self_attention",
    "GPipeTrainer", "PipelineParallel", "stack_stage_params", "ShardedTrainer",
    "tp_param_shardings", "init_distributed", "shutdown_distributed",
    "is_multihost", "global_array", "replicate_global",
    "DataParallelStep", "GradExchange", "data_axis_size", "data_sharded",
    "ElasticRuntime", "FileStore", "Membership", "MembershipChanged", "View",
    "MeshTrainer", "shard_update_spec",
    "threshold_encode", "threshold_decode", "pack_ternary", "unpack_ternary",
    "encode_packed", "decode_gathered", "packed_nbytes",
]
