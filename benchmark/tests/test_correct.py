"""``correct`` at a size a test run can hold: the plain references agree
with the program; the control (the reference in the next lower precision,
put in the program's place) comes out NOT correct; and with the timed path
broken underneath, the rest of a run sees ``correct`` come out false.

The sizes lie between the files' toy ``rehearsal`` blocks and the real
ones, and the limits here are this size's own (CPU readings, seeds 1-4:
the program's change_norm reads 0.008-0.012 and the fp8 control's
0.021-0.027; losses agree to 3e-5).  The cells' limits are set from chip
readings at the cells' sizes (PERF.md)."""

import numpy as np
import pytest

from harness import compare, resolve

TRAIN = "gpt2-medium.train.seq1024"
SERVE = "gpt2-medium.serve.batch-generate"
MID = {"vocab_size": 4096, "n_positions": 128, "n_ctx": 128, "n_embd": 128,
       "n_layer": 4, "n_head": 4, "n_inner": 512}
TRAIN_MIX = {"batch": 8, "reference_rows": 4,
             "data": {"kind": "markov_tokens", "seq_len": 128, "batches": 4},
             "limits": {"loss1": 1e-3, "loss2": 1e-3, "loss3": 1e-3,
                        "grad_norm": 0.03, "change_norm": 0.016}}
SERVE_MIX = {"check_requests": 12,
             "limits": {"logit_gap_max": 1e-4, "logit_gap_mean": 1e-5}}


def train_session(seed=2):
    cell = resolve.Cell(TRAIN)
    s = cell.driver.Session(cell, seed, True, (MID, TRAIN_MIX))
    s.make_data()
    return s


def run_program(s):
    s.build()
    s.run(0.2)
    got = s.program_reading()
    s.free()
    return got


@pytest.fixture(scope="module")
def train_reference():
    return train_session().reference()


def test_training_program_agrees_with_reference(train_reference):
    s = train_session()
    checks = s.compare(run_program(s), train_reference)
    assert compare.all_ok(checks), checks
    assert {c["name"] for c in checks} == {"loss1", "loss2", "loss3",
                                           "grad_norm", "change_norm"}
    # the key bias has no gradient under softmax: its units are left out
    # of the change by the rule on the reference's gradient, not by name
    assert checks[-1]["left_out"] == MID["n_layer"]


def test_training_control_is_not_correct(train_reference):
    s = train_session()
    checks = s.compare(s.reference(mode="fp8"), train_reference)
    assert not compare.all_ok(checks), checks


@pytest.mark.parametrize("fault", ["half_batch", "state_unchanged"])
def test_training_fault_in_the_reference_is_not_correct(train_reference,
                                                        fault):
    s = train_session()
    checks = s.compare(s.reference(fault=fault), train_reference)
    assert not compare.all_ok(checks), checks
    if fault == "state_unchanged":
        by = {c["name"]: c["value"] for c in checks}
        assert by["change_norm"] == pytest.approx(1.0, abs=1e-3)


def test_a_step_that_returns_its_state_unchanged(train_reference,
                                                 monkeypatch):
    """The timed path broken underneath: the program's Adam hands back
    what it was given."""
    from bigdl_tpu.optim.optim_method import Adam

    monkeypatch.setattr(Adam, "update",
                        lambda self, grads, state, params: (params, state))
    s = train_session()
    checks = s.compare(run_program(s), train_reference)
    assert not compare.all_ok(checks), checks


def test_half_of_the_batch_left_out(train_reference, monkeypatch):
    """The timed path broken underneath: the criterion averages over the
    first half of the rows only."""
    from bigdl_tpu.nn.criterion import TimeDistributedCriterion

    whole = TimeDistributedCriterion.apply

    def half(self, input, target):
        n = input.shape[0] // 2
        return whole(self, input[:n], target[:n])

    monkeypatch.setattr(TimeDistributedCriterion, "apply", half)
    s = train_session()
    checks = s.compare(run_program(s), train_reference)
    assert not compare.all_ok(checks), checks


# ------------------------------------------------------------------ #

def serve_run(seed=1, alter=None):
    from harness.device import CompileCount

    cell = resolve.Cell(SERVE)
    s = cell.driver.Session(cell, seed, True, (MID, SERVE_MIX))
    s.build()
    if alter is not None:
        alter(s)
    s.warm(CompileCount())
    window = s.run(2.5, 0.0)
    sample = s.sample()
    s.free()
    return s, window, sample


def test_serving_program_agrees_and_control_does_not():
    s, window, sample = serve_run()
    assert window["requests_failed"] == 0 and len(sample) >= 3
    gaps = s.reference_gaps(sample)
    assert len(gaps) == sum(len(r.tokens) for r in sample)
    assert compare.all_ok(s.compare(gaps, window))
    control = s.reference_gaps(sample, control="bf16")
    assert not compare.all_ok(s.compare(control, window))


def test_a_token_altered_where_it_is_produced():
    """The timed path broken underneath: every fifth token the scheduler
    delivers is another one."""
    def alter(s):
        sched = s.engine._generation()
        deliver = sched._deliver

        def broken(index, slot, done_lat):
            if len(slot.tokens) % 5 == 0:
                slot.tokens[-1] = (slot.tokens[-1] + 1) % s.cfg["vocab_size"]
                slot.last = slot.tokens[-1]
            return deliver(index, slot, done_lat)

        sched._deliver = broken

    s, window, sample = serve_run(alter=alter)
    gaps = s.reference_gaps(sample)
    assert not compare.all_ok(s.compare(gaps, window))
    assert gaps.max() > 0.1
