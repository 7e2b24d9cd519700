"""``harness.CompileLog`` names each program compiled after a snapshot,
and a call that JAX's caches answer adds nothing."""

import harness


def test_a_program_compiled_after_a_snapshot_is_named():
    import jax
    import jax.numpy as jnp

    log = harness.CompileLog()

    def add_two(x):
        return x + 2

    f = jax.jit(add_two)
    small, wide = jnp.ones(3), jnp.ones((2, 5))
    f(small).block_until_ready()
    before = log.snapshot()
    f(wide).block_until_ready()  # a new shape compiles
    diff = harness.CompileLog.since(before, log.snapshot())
    assert diff["compiles"] == 1 and diff["names"] == {"jit(add_two)": 1}
    assert diff["compile_s"] > 0

    before = log.snapshot()
    f(wide).block_until_ready()  # cached: nothing compiles
    diff = harness.CompileLog.since(before, log.snapshot())
    assert diff["compiles"] == 0 and diff["names"] == {}


def test_a_compile_event_without_a_name_counts_as_unnamed():
    log = harness.CompileLog()
    before = log.snapshot()
    log._duration("/jax/core/compile/backend_compile_duration", 0.5)
    diff = harness.CompileLog.since(before, log.snapshot())
    assert diff["compiles"] == 1 and diff["names"] == {"?": 1}
