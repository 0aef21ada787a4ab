"""paddle_tpu: a TPU-native deep-learning framework with PaddlePaddle
(hanzia/Paddle, early-2018) capability parity.

Fluid-style surface: build a Program with `layers`, differentiate with
`append_backward` / `Optimizer.minimize`, run with `Executor` — but execution
is whole-program XLA compilation on TPU (see core/executor.py) instead of a
per-op interpreter, and multi-device runs are SPMD over a jax Mesh (see
parallel/) instead of NCCL op-handles.

Usage mirrors the reference:

    import paddle_tpu as fluid            # or: import paddle_tpu.fluid as fluid
    x = fluid.layers.data("x", [784])
    y = fluid.layers.fc(x, 10, act="softmax")
    ...
    exe = fluid.Executor(fluid.TPUPlace(0))
"""

from . import ops as _ops_registration  # noqa: F401  (registers lowerings)
from . import layers  # noqa: F401
from . import initializer  # noqa: F401
from . import regularizer  # noqa: F401
from . import clip  # noqa: F401
from . import optimizer  # noqa: F401
from .core import (  # noqa: F401
    Block, CPUPlace, CUDAPinnedPlace, CUDAPlace, Executor, LoDTensor,
    Operator, Parameter, Program, Scope, TPUPlace, Variable, append_backward,
    calc_gradient, create_lod_tensor, default_main_program,
    default_startup_program, global_scope, gradients, is_compiled_with_cuda,
    is_compiled_with_tpu, pack_sequences, program_guard, scope_guard,
    switch_main_program, switch_startup_program, unique_name,
)
from .core import backward  # noqa: F401
from .param_attr import ParamAttr, WeightNormParamAttr  # noqa: F401
from .data_feeder import DataFeeder  # noqa: F401
from . import dataset  # noqa: F401
from . import reader  # noqa: F401
from .reader import batch  # noqa: F401
from . import io  # noqa: F401
from . import nets  # noqa: F401
from . import metrics  # noqa: F401
from . import average  # noqa: F401
from . import evaluator  # noqa: F401
from . import profiler  # noqa: F401
from . import debugger  # noqa: F401
from . import recordio  # noqa: F401
from . import concurrency  # noqa: F401
from .transpiler import (  # noqa: F401
    InferenceTranspiler, memory_optimize, release_memory,
)
from . import amp  # noqa: F401
from . import flags  # noqa: F401
from . import monitor  # noqa: F401

# PADDLE_TPU_MONITOR=1 arms runtime telemetry for the whole process
monitor.maybe_enable_from_flags()
from . import resilience  # noqa: F401

# PADDLE_TPU_FAULTS='{"rpc": {...}}' arms a seeded fault-injection plan
resilience.faults.maybe_arm_from_flags()
from . import trace  # noqa: F401

# PADDLE_TPU_TRACE[=rate] arms cross-process distributed tracing (span
# context rides the RPC frames; merge the fleet's span logs with
# `python -m paddle_tpu.trace merge`)
trace.maybe_enable_from_flags()
from . import serving  # noqa: F401
from . import distributed  # noqa: F401

# PADDLE_TPU_TELEMETRY=1 arms the scrape-only fleet-telemetry endpoint
# (needs the distributed tier imported: it serves the shared RPC frames)
from .monitor import collector as _collector  # noqa: E402

_collector.maybe_arm_from_flags()
from .distributed import DistributeTranspiler  # noqa: F401
from .core.selected_rows import SelectedRows  # noqa: F401
from . import parallel  # noqa: F401
from .parallel import ParallelExecutor  # noqa: F401

__version__ = "0.1.0"

# `import paddle_tpu as paddle; paddle.fluid...` compatibility: the package
# itself *is* the fluid namespace, and also exposes itself as `.fluid`.
import sys as _sys
fluid = _sys.modules[__name__]
_sys.modules[__name__ + ".fluid"] = fluid
