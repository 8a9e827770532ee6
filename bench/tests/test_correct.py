"""``correct`` at a size a CPU test can hold: the tiny cell (the
smollm-360m configuration's shape at toy widths, 2 rows of 256 tokens),
driven through the whole run as on the chip, with the limits of the
one-chip smollm cell.  A sound run is correct; the control and each
fault a training cell can have on one chip are not."""
import time

import pytest

import tiny
from harness import compare, main

LIMITS = compare.limits("smollm-360m.prolong-4k")
SEED = 2 ** 31 + 20251018


def run(seed=SEED, keep=None):
    return main.run_cell(tiny.tiny_cell(), seed, 0.3, False,
                         t_start=time.perf_counter(), limits=LIMITS,
                         chips=1, allow_cpu=True, keep=keep)


def test_sound_run_is_correct_and_control_is_not():
    keep = {}
    out = run(keep=keep)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] >= 1 and out["failed"] == 0
    control = compare.numbers(compare.reference_readings(
        keep["ref"], keep["config"], keep["key"], keep["batches"],
        rnd=compare.fp8), keep["reference"])
    assert not compare.verdict(control, LIMITS), control


def _unchanged_state(monkeypatch):
    import repro.train.trainer as trainer
    real = trainer.make_train_step

    def make(cfg, ctx, opt):
        step = real(cfg, ctx, opt)

        def frozen(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return frozen
    monkeypatch.setattr(trainer, "make_train_step", make)


def _half_batch(monkeypatch):
    import repro.train.step as step
    real = step.lm_loss

    def half(logits, labels, segment_ids):
        h = max(1, labels.shape[0] // 2)
        return real(logits[:h], labels[:h], segment_ids[:h])
    monkeypatch.setattr(step, "lm_loss", half)


def _answer_altered(monkeypatch):
    import repro.kernels.packed_flash.ops as ops
    real = ops.ca_server_attention

    def altered(q_tasks, *args):
        return real(q_tasks, *args).at[0].set(0.0)
    monkeypatch.setattr(ops, "ca_server_attention", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _answer_altered],
                         ids=["unchanged_state", "half_batch",
                              "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]
