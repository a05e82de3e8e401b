"""The compile clock counts the programs its own thread compiles, so a
compile on another thread of the process never reads as one inside the
measured window."""
import threading

import jax
import jax.numpy as jnp

from bench.harness.clock import CompileClock


def test_counts_only_its_own_thread():
    clock = CompileClock()
    before = clock.compiles()
    t = threading.Thread(target=lambda: jax.jit(lambda x: x * 3 + 1)(
        jnp.ones(7)).block_until_ready())
    t.start()
    t.join()
    assert clock.compiles() == before
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(9)).block_until_ready()
    assert clock.compiles() > before


def test_names_what_compiled_since_a_mark():
    clock = CompileClock()
    mark = clock.compiles()

    def seven_times(x):
        return x * 7

    jax.jit(seven_times)(jnp.ones(11)).block_until_ready()
    assert "seven_times" in clock.compiled_since(mark)
    assert clock.compiled_since(clock.compiles()) == {}
