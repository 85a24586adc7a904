"""Sweep-harness tests: mini cross-product sweep, derived metrics, plots."""

import os

import numpy as np
import pandas as pd
import pytest

from distributed_training_with_pipeline_parallelism_tpu.utils.sweep import (
    compute_speedup_and_efficiency, pivot_throughput, run_all_experiments,
    run_one_experiment, summarize_dynamics)
from distributed_training_with_pipeline_parallelism_tpu.utils import plotting


@pytest.fixture(scope="module")
def mini_sweep_df():
    # Tiny GPT-2 model, all three schedules, 2 devices (simulated CPU mesh).
    df = run_all_experiments(layers=(4,), heads=(4,), devices=(2,),
                             batch_size=8, seq_length=16, num_iterations=2,
                             dim=32, vocab_size=64, arch="gpt2",
                             verbose=False)
    return df


def test_sweep_schema(mini_sweep_df):
    df = mini_sweep_df
    assert len(df) == 3  # 1 layer x 1 head x 1 device count x 3 schedules
    for col in ("n_layers", "n_heads", "num_processes", "schedule",
                "elapsed_time", "throughput", "tokens_processed",
                "throughput_per_chip", "bubble_analytic", "bubble_simulated"):
        assert col in df.columns, col
    assert (df["tokens_processed"] == 8 * 16 * 2).all()
    assert (df["throughput"] > 0).all()


def test_interleaved_virtual_stage_rule(mini_sweep_df):
    df = mini_sweep_df
    il = df[df["schedule"] == "Interleaved1F1B"].set_index("num_processes")
    # L=4, D=2: 4 % (2*2) == 0 -> 2 virtual stages
    assert il.loc[2, "n_virtual"] == 2


def test_bfs_virtual_stage_rule():
    # BFS with V=1 degenerates to GPipe by construction, so the sweep rule
    # gives it the same 2-chunk treatment as Interleaved (ADVICE r1 #1)
    from distributed_training_with_pipeline_parallelism_tpu.utils.config import (
        virtual_stages_for)
    assert virtual_stages_for("BFS", 4, 2) == 2
    assert virtual_stages_for("BFS", 4, 4) == 1  # 4 % 8 != 0
    assert virtual_stages_for("Interleaved1F1B", 4, 4) == 1
    assert virtual_stages_for("GPipe", 4, 2) == 1


def test_speedup_and_efficiency(mini_sweep_df):
    sp = compute_speedup_and_efficiency(mini_sweep_df)
    assert len(sp) == 2  # 2 schedules x 1 device count
    for r in sp.itertuples():
        assert r.efficiency == pytest.approx(r.speedup / r.num_processes * 100)
    # sanity: speedups are in a plausible band (not zero/inf)
    assert sp["speedup"].between(0.05, 20).all()


def test_pivot_table(mini_sweep_df):
    pv = pivot_throughput(mini_sweep_df)
    assert pv.shape == (1, 3)


def test_error_contract():
    # impossible config: n_layers not divisible into stages
    out = run_one_experiment(n_layers=5, n_heads=4, num_devices=2,
                             schedule_type="GPipe", batch_size=4,
                             seq_length=8, num_iterations=1, dim=32,
                             vocab_size=64)
    assert "error" in out


def test_dynamics_columns_none_when_off(mini_sweep_df):
    # the model-health columns exist on every row so sweeps with and
    # without the dynamics probe concatenate cleanly; all-None here
    for col in ("grad_norm_final", "gns", "n_skipped_attributed"):
        assert col in mini_sweep_df.columns, col
        assert mini_sweep_df[col].isna().all()
    # and an all-None sweep summarizes to an empty frame (schema intact)
    summ = summarize_dynamics(mini_sweep_df)
    assert summ.empty
    assert list(summ.columns) == ["schedule", "n", "grad_norm_final_median",
                                  "gns_median", "n_skipped_attributed"]


def test_dynamics_row():
    out = run_one_experiment(n_layers=4, n_heads=4, num_devices=2,
                             schedule_type="1F1B", batch_size=8,
                             seq_length=16, num_iterations=1, dim=32,
                             vocab_size=64, n_microbatches=4, dynamics=True)
    assert "error" not in out
    assert isinstance(out["grad_norm_final"], float)
    assert out["grad_norm_final"] > 0
    assert out["gns"] is not None  # M=4 > 1 -> the GNS estimate ran
    assert out["n_skipped_attributed"] == 0  # no anomaly guard in a sweep row
    # run_all_experiments is what stamps the grid keys onto each row
    summ = summarize_dynamics(pd.DataFrame([{**out, "schedule": "1F1B"}]))
    assert len(summ) == 1
    row = summ.iloc[0]
    assert row["schedule"] == "1F1B" and row["n"] == 1
    assert row["grad_norm_final_median"] == pytest.approx(
        out["grad_norm_final"])
    assert row["gns_median"] == pytest.approx(out["gns"])


def test_summarize_dynamics_aggregation():
    # pure-pandas: rows the probe did not run for are excluded, per-row
    # missing gns drops out of the median, skips sum per schedule
    df = pd.DataFrame([
        {"schedule": "GPipe", "grad_norm_final": 1.0, "gns": 4.0,
         "n_skipped_attributed": 0},
        {"schedule": "GPipe", "grad_norm_final": 3.0, "gns": None,
         "n_skipped_attributed": 2},
        {"schedule": "1F1B", "grad_norm_final": None, "gns": None,
         "n_skipped_attributed": None},
    ])
    s = summarize_dynamics(df).set_index("schedule")
    assert list(s.index) == ["GPipe"]  # the all-None 1F1B row is excluded
    assert s.loc["GPipe", "n"] == 2
    assert s.loc["GPipe", "grad_norm_final_median"] == pytest.approx(2.0)
    assert s.loc["GPipe", "gns_median"] == pytest.approx(4.0)
    assert s.loc["GPipe", "n_skipped_attributed"] == 2
    # a frame without the columns at all (pre-dynamics sweep artifact)
    legacy = pd.DataFrame([{"schedule": "GPipe", "throughput": 1.0}])
    assert summarize_dynamics(legacy).empty


def test_plots(mini_sweep_df, tmp_path):
    sp = compute_speedup_and_efficiency(mini_sweep_df)
    p1 = tmp_path / "speedup.png"
    p2 = tmp_path / "grid.png"
    plotting.plot_speedup_and_efficiency(sp, str(p1))
    plotting.plot_throughput_grid(mini_sweep_df, str(p2))
    assert p1.stat().st_size > 0 and p2.stat().st_size > 0


def test_schedule_timeline_plots(tmp_path):
    """Timeline diagrams render from compiled tick tables for every builtin
    schedule family (reference Part 1 cells 4/7/9/11, made exact)."""
    from distributed_training_with_pipeline_parallelism_tpu.parallel.schedules import (
        compile_schedule)
    for name, D, V, M in [("GPipe", 4, 1, 4), ("1F1B", 4, 1, 4),
                          ("Interleaved1F1B", 4, 2, 8), ("ZBH1", 4, 1, 8),
                          ("ZBV", 4, 2, 8), ("BFS", 4, 2, 8)]:
        p = tmp_path / f"{name}.png"
        plotting.plot_schedule_timeline(name, D, V, M, path=str(p))
        assert p.stat().st_size > 0
    # the CompiledSchedule overload renders identically
    cs = compile_schedule("1F1B", 2, 1, 4)
    fig = plotting.plot_schedule_timeline(cs)
    assert fig is not None
