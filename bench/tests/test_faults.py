"""A run with the timed path broken underneath comes out not correct: once
for each fault the cells can have. (One chip: no exchange between chips to
leave out.) The harness's look for a chip is skipped; the rest of the run is
the benchmark's own."""
import pytest

from bench.run import run_cell


def _run(bench):
    return run_cell("tiny.train", seed=7, seconds=1.0, trace=False, bench=bench,
                    require_tpu=False)


@pytest.fixture
def broken_step(monkeypatch):
    """Replace the Trainer's step with ``fault(step)``."""
    from repro.training import trainer

    def plant(fault):
        make = trainer.make_train_step
        monkeypatch.setattr(trainer, "make_train_step",
                            lambda loss_fn, opt: fault(make(loss_fn, opt)))
    return plant


def test_step_returning_its_state_unchanged(tiny_bench, broken_step):
    def unchanged(step):
        def s(params, opt_state, batch, rng):
            return (params, opt_state, step(params, opt_state, batch, rng)[2])
        return s

    broken_step(unchanged)
    out = _run(tiny_bench)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out(tiny_bench, broken_step):
    def half(step):
        def s(params, opt_state, batch, rng):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt_state, {k: v[:n] for k, v in batch.items()}, rng)
        return s

    broken_step(half)
    assert _run(tiny_bench)["correct"] is False


def test_aggregate_that_drops_a_peer(tiny_bench, monkeypatch):
    from repro.core import strategies

    aggregate = strategies.FedAvg.aggregate
    monkeypatch.setattr(strategies.FedAvg, "aggregate",
                        lambda self, own, peers: aggregate(self, own, peers[:-1]))
    out = _run(tiny_bench)
    assert out["correct"] is False
    assert out["checks"]["agg_gap"]["value"] > out["checks"]["agg_gap"]["limit"]
