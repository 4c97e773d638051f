"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision step down, in the program's
place) fails the cell's limits.  Tiny sizes on the CPU; the harness's look
for a chip is skipped, the rest of a run is driven as on the chip."""
import pytest

import controls
import tiny


def run(workload, tmp_path, seed=5):
    return tiny.run_cell(tiny.tiny_cell(workload), seed=seed, seconds=1.0,
                         out_dir=str(tmp_path))


def assert_caught(res):
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


# ------------------------------------------------------------------ faults

def alter_top_id(idx, vocab):
    return idx.at[..., 0].set((idx[..., 0] + 1) % vocab)


def test_teacher_answer_altered(tmp_path, monkeypatch):
    from repro.serve import engine
    orig = engine.StreamingEngine.forward_topk
    vocab = tiny.TINY_MODEL["n_senones"]

    def forward_topk(self, batch):
        vals, idx = orig(self, batch)
        return vals, alter_top_id(idx, vocab)
    monkeypatch.setattr(engine.StreamingEngine, "forward_topk", forward_topk)
    assert_caught(run("teacher-gen", tmp_path))


def unchanged():
    """A strategy update that reports the loss and returns the state
    unchanged but for the step counter."""
    def make_update(self, loss_fn):
        def update(state, batch, lr):
            _, m = loss_fn(state.params, batch)
            return state.replace(step=state.step + 1), m
        return update
    return make_update


def test_step_returns_state_unchanged(tmp_path, monkeypatch):
    from repro.train import strategies
    monkeypatch.setattr(strategies.Local, "make_update", unchanged())
    assert_caught(run("student-distill", tmp_path))


def test_half_batch_left_out(tmp_path, monkeypatch):
    from repro.launch import steps
    orig = steps.make_loss_fn

    def make_loss_fn(*a, **kw):
        loss = orig(*a, **kw)

        def half(params, batch, rng=None):
            h = batch["feats"].shape[0] // 2
            return loss(params, {k: v[:h] for k, v in batch.items()})
        return half
    monkeypatch.setattr(steps, "make_loss_fn", make_loss_fn)
    assert_caught(run("student-distill", tmp_path))


# ---------------------------------------------------------------- controls

@pytest.mark.parametrize("workload", ["teacher-gen", "student-distill"])
def test_control_fails_the_limits(workload, tmp_path):
    cell = tiny.tiny_cell(workload)
    out = controls.readings(cell, 11, 0.5, True, str(tmp_path))
    assert all(v <= cell.limits[k] for k, v in out["program"].items()), out
    assert any(v > cell.limits[k] for k, v in out["fp8"].items()), out
