"""What decides ``correct`` is shown to fail: the control (the service at
its own lower precisions: 8 noise planes where the configuration states 16,
the per-core BER table in bfloat16) and each fault of the timed path that a cell can have, planted under a run
that skips the look for a chip."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import pytest

import run

SEED = 2**33 + 5


def _checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


def test_control_lower_precisions_are_not_correct():
    r = run.run_cell("whype-closed", SEED, 0.5, False, rehearse=True,
                     control=True)
    assert r["correct"] is False
    assert _checks(r)["maxsim_mismatch"] > 0
    assert _checks(r)["link_ber_gap"] > run.LINK_BER_GAP_LIMIT


def _alter_answer(eng):
    step = eng.step

    def bad(params, state):
        state, (pred, maxsim) = step(params, state)
        return state, (pred.at[:, 0].add(1), maxsim)

    eng.step = bad


def _stale_answer(eng):
    step, last = eng.step, {}

    def bad(params, state):
        state, out = step(params, state)
        prev = last.get("out", out)
        last["out"] = out
        return state, prev

    eng.step = bad


def _half_batch(eng):
    step = eng.step

    def bad(params, state):
        b = state["queries"].shape[1]
        q = state["queries"].at[:, b // 2:].set(0)
        return step(params, dict(state, queries=q))

    eng.step = bad


@pytest.mark.parametrize("fault", [_alter_answer, _stale_answer, _half_batch],
                         ids=["answer_altered", "state_unchanged",
                              "half_batch_left_out"])
def test_fault_in_the_timed_path_is_not_correct(fault):
    r = run.run_cell("table1-closed", SEED, 0.5, False, rehearse=True,
                     tamper=fault)
    assert r["correct"] is False, r["checks"]
    assert _checks(r)["pred_mismatch"] + _checks(r)["maxsim_mismatch"] > 0


def test_answer_that_never_comes_is_not_correct(monkeypatch):
    from repro.serving import HDCScheduler

    collect = HDCScheduler._collect

    def lose_slot_0(self, emitted):
        lost = self.running.get(0)
        done = collect(self, emitted)
        if lost is not None:
            self.results.pop(lost[0].rid, None)
        return [d for d in done if lost is None or d.rid != lost[0].rid]

    monkeypatch.setattr(HDCScheduler, "_collect", lose_slot_0)
    r = run.run_cell("table1-closed", SEED, 0.5, False, rehearse=True)
    assert r["correct"] is False and _checks(r)["unanswered"] > 0

