"""fluid.layers-compatible DSL surface."""

from . import ops  # noqa: F401
from .conv_layers import (  # noqa: F401
    conv2d, conv2d_transpose, conv3d, conv3d_transpose, pool2d, pool3d,
    roi_pool, row_conv, spp,
)
from .io_ops import data  # noqa: F401
from . import learning_rate_scheduler  # noqa: F401
from .learning_rate_scheduler import (  # noqa: F401
    exponential_decay, inverse_time_decay, natural_exp_decay, noam_decay,
    piecewise_decay, polynomial_decay,
)
from .nn import *  # noqa: F401,F403
from .nn import (  # noqa: F401
    accuracy, auc, batch_norm, cross_entropy, dropout, embedding, fc,
    layer_norm, matmul, mean, one_hot, reduce_max, reduce_mean, reduce_min,
    reduce_prod, reduce_sum, softmax, softmax_with_cross_entropy,
    square_error_cost, topk,
    block_diffusion_attention, block_diffusion_noise, qk_norm_rope,
    rms_norm, rope, silu_mul, hyper_connection, mla_attention,
    causal_attention, sigmoid_mul, exit_distribution,
)
from .ops import *  # noqa: F401,F403
from .math_ops import scale  # noqa: F401
from .parallel_layers import (  # noqa: F401
    pipelined_decoder_stack, routed_experts, sequence_parallel_attention,
    sparse_moe,
)
from .sequence_layers import *  # noqa: F401,F403
from .state_space import *  # noqa: F401,F403
from .compat import *  # noqa: F401,F403
from .control_flow import *  # noqa: F401,F403
from . import control_flow  # noqa: F401
from .rnn_layers import *  # noqa: F401,F403
from .tensor import (  # noqa: F401
    argmax, argmin, assign, cast, concat, create_global_var, create_tensor,
    expand, fill_constant, fill_constant_batch_size_like, gather, increment,
    ones, reshape, scatter, slice, split, step_sum, sums, transpose, zeros,
)
