"""Demonstration models built ON the framework.

The reference is a collectives library, not a trainer — its "application
layer" is MPI-style host programs and device kernels (``test/host``,
``vadd_put``).  The TPU-native equivalent of those applications is a
distributed model whose every communication edge goes through
``accl_tpu.ops``: a tensor/data-parallel transformer (``transformer.py``)
and ring attention for sequence parallelism (``ring_attention.py``) —
the long-context layer SURVEY.md §5 notes the reference's segmented-ring
machinery is the substrate for.
"""

from .transformer import (
    BlockDiffusion,  # noqa: F401
    DeltaAttention,  # noqa: F401
    HeadGeometry,  # noqa: F401
    LatentAttention,  # noqa: F401
    LayerKind,  # noqa: F401
    Mamba2,  # noqa: F401
    TransformerConfig,
    YarnScaling,  # noqa: F401
    diffusion_noise,  # noqa: F401
    generate,
    hybrid_layers,  # noqa: F401
    init_params,
    forward,
    make_sharded_generate,
    make_sharded_train_step,
    make_sharded_forward,
    make_sharded_router_probe,
    prefill,
)
from .ring_attention import (  # noqa: F401
    reference_attention,
    ring_attention,
    stripe_sequence,
    striped_attention,
    unstripe_sequence,
)
from ..ops.pallas.attention import (  # noqa: F401
    ring_attention as ring_attention_pallas,
)
from .ulysses_attention import ulysses_attention  # noqa: F401
from .moe import init_moe_params, moe_ffn  # noqa: F401
from .encoder import (  # noqa: F401
    encode,
    encoder_forward,
    make_sharded_encoder_step,
    mlm_loss,
)
from .composed import (  # noqa: F401
    interleave_layer_order,
    make_pp_train_step,
    stack_params,
    stacked_param_specs,
    unstack_params,
)
from .pipeline import (  # noqa: F401
    pipeline_apply,
    pipeline_apply_interleaved,
    pipeline_bubble_fraction,
    pipeline_loss,
    pipeline_loss_and_grads,
    pipeline_loss_and_grads_1f1b,
)
