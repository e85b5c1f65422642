"""``correct`` at a size a test can hold: the harness's look for a chip is
skipped, the rest of a run is driven on the CPU, and with the timed path
broken underneath ``correct`` comes out false. The committed limits of
each cell are the ones held."""
import checks
import harness as H
from conftest import tiny_ctx

TRAIN = "smollm-360m.train-8k"


def train_run():
    drv = H.load_module("drivers", "train")
    return drv.run(tiny_ctx(TRAIN))


def test_train_sound_run_is_correct():
    res = train_run()
    assert res.correct, res.checks
    assert res.attempted > 0 and res.failed == 0


def test_train_step_that_returns_its_state_unchanged_fails(monkeypatch):
    from repro.train import step as st
    make = st.make_train_step

    def frozen(model, tc):
        real = make(model, tc)

        def step(params, opt, batch):
            _, _, m = real(params, opt, batch)
            return params, opt, m
        return step
    monkeypatch.setattr(st, "make_train_step", frozen)
    res = train_run()
    assert not res.correct
    assert {c.name for c in res.checks if not c.ok} >= {"update_leaf"}


def test_train_half_batch_left_out_fails(monkeypatch):
    from repro.models import layers
    ce = layers.cross_entropy

    def half(logits, labels, mask=None):
        labels = labels.at[:, labels.shape[1] // 2:].set(-100)
        return ce(logits, labels, mask)
    monkeypatch.setattr(layers, "cross_entropy", half)
    res = train_run()
    assert not res.correct, res.checks


def test_train_control_fails():
    """The reference in float8, put in the program's place."""
    drv = H.load_module("drivers", "train")
    ctx = tiny_ctx(TRAIN)
    import traffic
    rows = traffic.train_rows(ctx.workload["traffic"],
                              ctx.config["vocab_size"], ctx.seed, 3)
    want = drv.reference_reading(ctx, rows)
    ctl = drv.reference_reading(ctx, rows, precision="fp8")
    got = checks.train_checks(ctl, want, ctx.workload["limits"])
    assert not all(c.ok for c in got), got
