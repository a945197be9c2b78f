"""Parallelism substrate: mesh axes, sharding rules, SP/PP/EP building blocks.

This subpackage is the capability the reference framework lacks but whose
substrate SURVEY.md §2.7/§5.7 requires the TPU build to provide: tensor,
pipeline, sequence/context (ring attention), and expert parallelism expressed
natively over a ``jax.sharding.Mesh`` with XLA collectives — instead of the
reference's answer of "more data-parallel replicas + better allreduce"
(ref: common/process_set.{h,cc} process sets and the raw alltoall primitive,
operations.cc:1642, are the closest the reference gets).

Canonical axis names (any subset may be present in a mesh, size-1 axes are
free):

* ``dp`` — data parallel (gradient allreduce; the reference's whole world)
* ``fsdp`` — fully-sharded data parallel (param/grad reduce-scatter +
  all-gather; the ZeRO-style axis SURVEY.md §2.7 lists as absent upstream)
* ``pp`` — pipeline stages (microbatch circulation over ``ppermute``)
* ``tp`` — tensor (Megatron-style) parallel within a layer
* ``sp`` — sequence/context parallel (ring attention)
* ``ep`` — expert parallel (MoE alltoall token routing)
"""

from .mesh import (  # noqa: F401
    AXIS_DP,
    AXIS_FSDP,
    AXIS_PP,
    AXIS_TP,
    AXIS_SP,
    AXIS_EP,
    CANONICAL_AXES,
    MeshSpec,
    make_mesh,
    mesh_shape_for,
    pod_axis_tiers,
    pod_mesh_spec,
)
from .sharding import (  # noqa: F401
    batch_spec,
    logical_to_mesh,
    named_sharding,
    pcast_to_union,
    transformer_rules,
)
from .ring_attention import ring_attention  # noqa: F401
from .pipeline import (  # noqa: F401
    bubble_fraction,
    pipeline_1f1b,
    report_pipeline_mfu,
)
from .moe import (  # noqa: F401
    MoEAux,
    moe_capacity,
    moe_dispatch_combine,
    report_moe_aux,
)
