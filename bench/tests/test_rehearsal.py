"""A whole run of the harness on the CPU at a reduced size: the look for a
chip is skipped, everything else (weights, warm-up, window, the
comparison with the reference, the metrics) runs as on the chip. With the
timed path broken underneath (``harness.faults``), or with the float8
control in the program's place, ``correct`` must come out false."""
import json
import types
from pathlib import Path

import pytest

import run
from harness.faults import FAULTS

ROOT = Path(__file__).resolve().parents[2]
CELL = "qwen2.5-3b.decode_heavy"
# limits for this reduced size, from CPU rehearsals: sound runs (eight
# seeds for the first two, three for the rest) read a logit gap of 0 to
# 0.0052, a sign/exponent mismatch of 2.4-2.8%, a mantissa loss gap of
# 0.008-0.053 and ledger gaps under 0.006; the float8 control read gaps of
# 0.017-0.140, mismatches of 18.8-20.1% and a mantissa loss gap of 0.93;
# an exact write reads 1 on the last three, a float8 store 0.18 and more
LIMITS = {"logit_gap": 0.008, "kv_exp_mismatch": 0.06,
          "mantissa_loss_gap": 0.3, "write_energy_gap": 0.05,
          "write_error_gap": 0.05, "bad_requests": 0}


# two slot classes of cache (the window pattern's two positions), neither
# ring wrapped: the window of 128 holds the whole row of 96
TWO_CLASSES = [128, 0]


def rehearse(capsys, fault=None, seed=3_000_000_019, control=0,
             limits=LIMITS, rc=0, window_pattern=None):
    f = FAULTS[fault] if fault else None
    mix = dict(capacity=8, max_seq=96, prompt_lengths=(8, 16),
               prompt_counts=(1, 1), decode_min=8, decode_max=64, block=8,
               check_requests=8, max_requests=60)
    if f:
        mix["serve"] = f.serve
    r = types.SimpleNamespace(
        bench=json.loads((ROOT / "BENCHMARK.json").read_text()),
        model=dict(num_layers=4, d_model=128, num_heads=4, num_kv_heads=2,
                   head_dim=32, d_ff=256, vocab_size=4096),
        mix=mix, peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
        limits=limits, patch=f.patch if f else None)
    if window_pattern is not None:
        r.model["window_pattern"] = window_pattern
    got = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "1.5", "--trace", "0", "--control", str(control)],
                   rehearse=r)
    assert got == rc
    if rc:
        return capsys.readouterr()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(capsys):
    out = rehearse(capsys)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "step_ms", "setup_s"}
    assert out["info"]["compiles_in_window"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(LIMITS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(capsys, fault):
    out = rehearse(capsys, fault=fault)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("seed", [11, 14, 2**31 + 5])
def test_float8_control_is_not_correct(capsys, seed):
    out = rehearse(capsys, seed=seed, control=1)
    assert not out["correct"], out["checks"]
    c = out["checks"]
    assert c["logit_gap"]["value"] > LIMITS["logit_gap"]
    assert c["kv_exp_mismatch"]["value"] > LIMITS["kv_exp_mismatch"]
    assert c["mantissa_loss_gap"]["value"] > LIMITS["mantissa_loss_gap"]
    # the same run's program, read beside its control, is within limits
    assert all(out["info"]["numbers"][k] <= lim for k, lim in LIMITS.items())


def test_limits_naming_an_unknown_number_are_refused(capsys):
    out = rehearse(capsys, limits=dict(LIMITS, no_such_number=1.0), rc=1)
    assert out.out == "" and "no_such_number" in out.err


def test_two_slot_classes_sound_run_is_correct(capsys):
    out = rehearse(capsys, window_pattern=TWO_CLASSES)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["control"])
def test_two_slot_classes_broken_or_control_is_not_correct(capsys, fault):
    if fault == "control":
        out = rehearse(capsys, control=1, window_pattern=TWO_CLASSES)
    else:
        out = rehearse(capsys, fault=fault, window_pattern=TWO_CLASSES)
    assert not out["correct"], out["checks"]


def test_a_ring_the_longest_request_would_wrap_is_refused(capsys):
    # the longest request is 16 + 64 positions; a window of 32 keeps 32
    out = rehearse(capsys, window_pattern=[32, 0], rc=1)
    assert out.out == "" and "'slot0'" in out.err and "'slot1'" not in out.err
