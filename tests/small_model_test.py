"""What the four architecture files share (tests/test_latent_moe.py,
test_windowed_moe.py, test_hybrid_ssm.py, test_block_diffusion.py): a
benchmark architecture's small model built, given its SGD step and
initialised ONCE a file, since the startup program compiles for
seconds and both `test_small_model_*` tests want it. As tests/op_test.py
is: a module the files import, no test of its own."""

import numpy as np
import jax.numpy as jnp

import paddle_tpu as fluid


def build(arch_name, cfg, seq):
    """(arch, main, startup, forward, scope, cost, logits): `forward`
    is the for_test clone of the program as built."""
    from chipbench import cells
    arch = cells.load_arch(arch_name)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    scope = fluid.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        cost, logits = arch.build(cfg, seq)
        forward = main.clone(for_test=True)
    return arch, main, startup, forward, scope, cost, logits


def initialised(arch_name, cfg, seq, after_startup=None):
    """((arch, main, forward, scope, cost, logits, ...), drawn) for a
    module-scoped fixture: `forward` was cloned before `main` got its
    SGD step at rate 1, `after_startup(arch, scope)` may redraw
    parameters (what it returns joins the model's tuple), and `drawn`
    is what the scope then held."""
    arch, main, startup, forward, scope, cost, logits = build(
        arch_name, cfg, seq)
    model = (arch, main, forward, scope, cost, logits)
    with fluid.program_guard(main, startup), fluid.scope_guard(scope):
        fluid.optimizer.SGD(learning_rate=1.0).minimize(cost)
        fluid.Executor(fluid.CPUPlace()).run(startup)
        if after_startup is not None:
            model += (after_startup(arch, scope),)
    return model, {name: np.array(scope.find_var(name))
                   for name in scope.local_var_names()}


def as_initialised(model, drawn):
    """The model with what a test before this one moved in its scope
    put back: for the function-scoped fixture."""
    for name, value in drawn.items():
        model[3].set(name, jnp.asarray(value))
    return model
