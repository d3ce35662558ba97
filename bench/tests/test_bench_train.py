"""The training cell end to end at a tiny size on the CPU (the harness's
look for a chip skipped), with each fault this cell can have planted in
the timed path, and its fp8 control.  The tiny model computes in
float32, so a sound run reads near nothing against the limits."""

from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import control, load_module, run  # noqa: E402

SEED = 2 ** 31 + 5
TINY = {"model": {"hidden_size": 64, "intermediate_size": 128,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "head_dim": 16, "vocab_size": 512},
        "train": {"seq_len": 128, "batch": 4},
        "precision": {"params": "float32", "compute": "float32"},
        "corpus": {"n_seqs": 32, "unit_rows": 8},
        "store": {"object_bytes": 16384}}
REF = load_module(ROOT / "bench/configs/yi9b_2l_train_ref.py",
                  "ref_yi")


def cell():
    return run.main(["--workload", "yi9b_2l.train_packed", "--seed",
                     str(SEED), "--seconds", "0.2", "--trace", "0"],
                    allow_cpu=True, config_override=TINY)


def test_train_cell_runs_correct_at_a_tiny_size():
    res = cell()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"setup_s", "train_tokens_per_s"}
    assert set(res["checks"]) == {"first_loss_gap", "grad_norm_gap",
                                  "update_norm_gap",
                                  "under_replicated_objects"}
    assert list(res)[-1] == "checks"


def test_a_step_that_returns_its_state_unchanged_is_incorrect(monkeypatch):
    from repro.train import optimizer, steps

    def unchanged(cfg, grads, params, opt_state):
        return params, opt_state, optimizer.global_norm(grads)
    monkeypatch.setattr(steps, "adamw_update", unchanged)
    res = cell()
    assert not res["correct"]
    assert res["checks"]["update_norm_gap"]["value"] == pytest.approx(
        1.0, abs=1e-3)


def test_half_the_batch_left_out_is_incorrect(monkeypatch):
    from repro.train import trainer
    real = trainer.fused_batch
    monkeypatch.setattr(trainer, "fused_batch",
                        lambda packed: real(packed[:packed.shape[0] // 2]))
    res = cell()
    assert not res["correct"]
    assert res["checks"]["grad_norm_gap"]["value"] > \
        REF.LIMITS["grad_norm_gap"]


def test_fp8_control_and_half_batch_fail_the_comparison():
    line, = control.main(["--workload", "yi9b_2l.train_packed", "--seeds",
                          str(SEED)], config_override=TINY)
    for kind in ("control_fp8", "half_batch"):
        assert any(line[kind][k] > lim for k, lim in REF.LIMITS.items()), \
            (kind, line[kind])
