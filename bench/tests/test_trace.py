"""The reduction from a profiler trace to numbers, on a trace recorded here
on the CPU with JAX's profiler."""
import time

import jax
import jax.numpy as jnp
import pytest

from bench import trace as tr


def test_union_and_gaps():
    busy = tr.union([(3, 5), (0, 1), (0.5, 2), (4, 6), (9, 12)], 0.0, 10.0)
    assert busy == [(0, 2), (3, 6), (9, 10)]
    assert tr.gaps_of(busy, 0.0, 10.0) == [(2, 3), (6, 9)]
    assert tr.gaps_of([], 1.0, 2.0) == [(1.0, 2.0)]


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    log_dir = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def train_step(x):
        return jnp.tanh(x @ x)

    x = jnp.ones((256, 256), jnp.float32)
    train_step(x).block_until_ready()
    with tr.record(log_dir):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.compute"):
                x = train_step(x)
                x.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.05)
    return tr.reduce(log_dir, tr.cpu_layout())


def test_busy_union_lies_inside_the_window(summary):
    assert 0.15 <= summary.window_s < 5.0
    assert 0.0 < summary.busy_s < summary.window_s - 0.1  # three sleeps of 50 ms


def test_named_step_is_counted(summary):
    n, seconds = summary.module("train_step")
    assert n == 3 and seconds > 0


def test_gaps_are_named_by_host_span(summary):
    longest = [name for name, _ in summary.gaps[:3]]
    assert longest == ["bench.host_wait"] * 3
    assert all(0.04 < s < 0.5 for _, s in summary.gaps[:3])
    assert summary.host["bench.compute"][0] == 3


def test_ops_are_listed_largest_first(summary):
    secs = [s for _, s in summary.ops]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
