"""Layer library. Importing this package registers all layer types."""

from paddle_tpu.layers import (  # noqa: F401
    attention,
    base,
    basic,
    conv,
    cost,
    decoder,
    detection,
    extras,
    fused,
    fused_text,
    moe,
    norm,
    pool,
    recurrent,
    recurrent_group,
    sampling,
    sequence,
    steps,
    structured,
)
