"""Op lowering registry population: importing this package registers all op
lowerings (the analog of the reference's static REGISTER_OPERATOR blocks)."""

from . import (  # noqa: F401
    activations,
    beam_search,
    block_diffusion,
    causal_attention,
    control_flow,
    conv,
    crf_ctc,
    detection_ops,
    diff_attention,
    elementwise,
    fused,
    hyper_connection,
    latent_attention,
    rnn_ops,
    selective_scan,
    short_conv,
    loss,
    math,
    metrics_ops,
    nn,
    optimizer_ops,
    parallel_ops,
    sequence_ops,
    tensor_ops,
)
