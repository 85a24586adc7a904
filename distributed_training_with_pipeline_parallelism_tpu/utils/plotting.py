"""Result plots matching the reference notebook's figures (SURVEY.md C10).

- :func:`plot_speedup_and_efficiency` — cell 28 (``.ipynb:863-943``): a 1x2
  figure of speedup and scaling-efficiency lines vs model config ``L{n}_H{h}``,
  color by schedule, marker by device count, with the GPipe = 1.0 / 100%
  reference lines.
- :func:`plot_throughput_grid` — cell 30 (``.ipynb:955-1004``): a 3x3 grid of
  throughput-vs-device-count panels, one per (layers, heads).
- :func:`plot_schedule_timeline` — the reference Part 1's schedule-timeline
  diagrams (cells 4/7/9/11, ``.ipynb:30-171``), but *exact*: rendered from
  the compiled tick table the executor actually runs, for any schedule and
  any (D, V, M), bubbles included.
"""

from __future__ import annotations

from typing import Optional

import pandas as pd


def _mpl():
    import matplotlib
    # headless default — but do NOT clobber a notebook's inline backend,
    # or executed notebooks silently lose every figure
    if "inline" not in matplotlib.get_backend().lower():
        matplotlib.use("Agg")
    import matplotlib.pyplot as plt
    return plt

SCHEDULE_COLORS = {"GPipe": "tab:blue", "1F1B": "tab:orange",
                   "Interleaved1F1B": "tab:green",
                   "ZBH1": "tab:red", "BFS": "tab:purple",
                   "ZBV": "tab:brown"}
PROC_MARKERS = {2: "o", 4: "s", 8: "^", 16: "D"}


def plot_speedup_and_efficiency(speedup_df: pd.DataFrame,
                                path: Optional[str] = None):
    plt = _mpl()
    fig, (ax_s, ax_e) = plt.subplots(1, 2, figsize=(14, 5))
    configs = sorted({(r.n_layers, r.n_heads)
                      for r in speedup_df.itertuples()})
    labels = [f"L{L}_H{H}" for L, H in configs]
    xs = range(len(configs))
    for schedule, g1 in speedup_df.groupby("schedule"):
        for procs, g2 in g1.groupby("num_processes"):
            lookup = {(r.n_layers, r.n_heads): r for r in g2.itertuples()}
            ys_s = [lookup[c].speedup if c in lookup else None for c in configs]
            ys_e = [lookup[c].efficiency if c in lookup else None for c in configs]
            style = dict(color=SCHEDULE_COLORS.get(schedule),
                         marker=PROC_MARKERS.get(procs, "x"),
                         label=f"{schedule} ({procs} devices)")
            ax_s.plot(xs, ys_s, **style)
            ax_e.plot(xs, ys_e, **style)
    ax_s.axhline(1.0, color="gray", linestyle="--", label="GPipe baseline")
    ax_e.axhline(100.0, color="gray", linestyle="--")
    for ax, title, ylabel in ((ax_s, "Speedup vs GPipe", "speedup"),
                              (ax_e, "Scaling efficiency", "efficiency (%)")):
        ax.set_xticks(list(xs))
        ax.set_xticklabels(labels, rotation=45)
        ax.set_xlabel("model configuration")
        ax.set_ylabel(ylabel)
        ax.set_title(title)
        ax.grid(alpha=0.3)
    ax_s.legend(fontsize=8)
    fig.tight_layout()
    if path:
        fig.savefig(path, dpi=120)
    return fig


OP_COLORS = {"F": "#4e9ad1", "B": "#f29d4b", "W": "#8ec07c"}


def plot_schedule_timeline(name_or_cs, n_devices: int = None,
                           n_virtual: int = 1, n_microbatches: int = 4,
                           path: Optional[str] = None, ax=None,
                           annotate: bool = True):
    """Per-device schedule timeline rendered from the compiled tick table.

    The reference's Part 1 carries four hand-drawn schedule diagrams (cells
    4/7/9/11) as embedded PNGs; this renders the *actual* executed schedule:
    each row is a device, each cell a tick, colored by op (F blue / B orange
    / W green), labeled with the microbatch index, with virtual-stage chunks
    hatched by shade. Blank cells ARE the bubble — the figure is exact for
    any (schedule, D, V, M), including beyond-parity ones (ZBH1/ZBV/BFS and
    custom registrations).

    Accepts a schedule name + dims, or an already-compiled
    :class:`~..parallel.schedules.CompiledSchedule`.
    """
    from ..parallel.schedules import (CompiledSchedule, compile_schedule,
                                      placement_chunk_of, placement_device_of)
    if isinstance(name_or_cs, CompiledSchedule):
        cs = name_or_cs
    else:
        cs = compile_schedule(name_or_cs, n_devices, n_virtual, n_microbatches)
    D, V = cs.n_devices, cs.n_virtual
    plt = _mpl()
    if ax is None:
        fig, ax = plt.subplots(
            figsize=(max(6, 0.32 * cs.makespan), 0.6 * D + 1.2))
    else:
        fig = ax.figure

    for action, tick in cs.ticks.items():
        dev = placement_device_of(cs.placement, action.stage, D)
        chunk = placement_chunk_of(cs.placement, action.stage, D)
        from matplotlib.colors import to_rgb
        base = OP_COLORS[action.op]
        # deeper virtual chunks darken (the reference's diagrams shade the
        # second chunk of interleaved schedules the same way)
        shade = 1.0 - 0.35 * (chunk / max(1, V - 1)) if V > 1 else 1.0
        rgb = tuple(min(1.0, c * shade) for c in to_rgb(base))
        ax.add_patch(plt.Rectangle((tick, D - 1 - dev + 0.08), 1.0, 0.84,
                                   facecolor=rgb, edgecolor="white",
                                   linewidth=0.6))
        if annotate and cs.makespan <= 80:
            ax.text(tick + 0.5, D - 1 - dev + 0.5, str(action.microbatch),
                    ha="center", va="center", fontsize=7,
                    color="black")

    ax.set_xlim(0, cs.makespan)
    ax.set_ylim(0, D)
    ax.set_yticks([D - 1 - d + 0.5 for d in range(D)])
    ax.set_yticklabels([f"device {d}" for d in range(D)])
    ax.set_xlabel("tick")
    from ..parallel.schedules import simulated_bubble
    bub = simulated_bubble(cs, 1.0, 1.0)["bubble_fraction"]
    ax.set_title(f"{cs.name}  D={D} V={V} M={cs.n_microbatches}  "
                 f"(makespan {cs.makespan} ticks, unit-cost bubble "
                 f"{bub:.1%})", fontsize=10)
    handles = [plt.Rectangle((0, 0), 1, 1, facecolor=OP_COLORS[o])
               for o in ("F", "B", "W")]
    labels = ["forward", "backward (dgrad)" if cs.split_backward
              else "backward", "weight grad"]
    n_leg = 3 if cs.split_backward else 2
    ax.legend(handles[:n_leg], labels[:n_leg], fontsize=7, loc="lower right")
    fig.tight_layout()
    if path:
        fig.savefig(path, dpi=120)
    return fig


def plot_latency_curve(section, path: Optional[str] = None):
    """The serving SLO observatory's headline figure: latency vs offered
    load from a ``serving_load`` manifest section (``serving.loadgen.
    sweep_offered_load`` / ``scripts/serve_load.py``'s ``curve.json``).

    Left panel: p50/p99 TTFT and the admission-wait p99 against offered
    load (units of ring capacity), with the SLO's p99 TTFT budget as a
    horizontal line and the detected saturation knee as a vertical one —
    the hockey stick and where it breaks the budget, on one axis. Right
    panel: goodput and goodput-under-SLO, which flatten (then part ways)
    past the knee. ``section`` is the manifest dict; percentiles missing
    from a row (empty point) plot as gaps.
    """
    plt = _mpl()

    def col(key, pct=None):
        out = []
        for row in section.get("curve", []):
            v = row.get(key)
            if pct is not None:
                v = v.get(pct) if isinstance(v, dict) else None
            out.append(v if isinstance(v, (int, float)) else float("nan"))
        return out

    loads = [row.get("offered_load") for row in section.get("curve", [])]
    fig, (ax_l, ax_g) = plt.subplots(1, 2, figsize=(11, 4.2))
    ax_l.plot(loads, col("ttft_ticks", "p99"), marker="o",
              color="tab:red", label="TTFT p99")
    ax_l.plot(loads, col("ttft_ticks", "p50"), marker="o",
              color="tab:blue", label="TTFT p50")
    ax_l.plot(loads, col("admit_wait_ticks", "p99"), marker="s",
              color="tab:orange", linestyle="--", label="admission wait p99")
    slo = section.get("slo") or {}
    if isinstance(slo.get("ttft_p99_ticks"), (int, float)):
        ax_l.axhline(slo["ttft_p99_ticks"], color="gray", linestyle=":",
                     label=f"SLO p99 budget ({slo['ttft_p99_ticks']:g})")
    knee = section.get("knee") or {}
    for ax in (ax_l, ax_g):
        if isinstance(knee.get("knee_load"), (int, float)):
            ax.axvline(knee["knee_load"], color="black", linestyle="--",
                       alpha=0.6,
                       label=f"knee @ {knee['knee_load']:g} "
                             f"({knee.get('reason')})")
        ax.set_xlabel("offered load (x ring capacity)")
        ax.grid(alpha=0.3)
    ax_l.set_ylabel("latency (ticks)")
    ax_l.set_title("tail latency vs offered load")
    ax_l.legend(fontsize=8)
    ax_g.plot(loads, col("goodput"), marker="o", color="tab:green",
              label="goodput (tok/tick)")
    slo_good = [((row.get("slo") or {}).get("goodput_under_slo")
                 if isinstance((row.get("slo") or {})
                               .get("goodput_under_slo"), (int, float))
                 else float("nan"))
                for row in section.get("curve", [])]
    ax_g.plot(loads, slo_good, marker="s", color="tab:purple",
              linestyle="--", label="goodput under SLO")
    ax_g.set_ylabel("tokens / tick")
    ax_g.set_title("goodput vs offered load")
    ax_g.legend(fontsize=8)
    fig.tight_layout()
    if path:
        fig.savefig(path, dpi=120)
    return fig


def plot_queue_depth(summary, path: Optional[str] = None):
    """Queue depth and slot occupancy over ticks for one serving run —
    the open-loop early-warning picture: a queue ramp that precedes the
    TTFT blow-up by a trace length, against how full the ring's slots
    are while it builds.

    ``summary`` is a ``serving_summary`` dict (or a ``serving_load``
    curve row's nested ``summary``) carrying the block-boundary
    ``queue_depth`` / ``occupancy`` series as ``[[tick, n], ...]``; the
    ``n_slots`` ceiling is drawn when present. Step-drawn: each sample
    holds until the next block boundary (the fast-forward boundary
    samples make idle gaps render as zeros, not interpolated slopes).
    """
    plt = _mpl()
    fig, ax = plt.subplots(figsize=(9, 3.6))
    for key, color, label in (("queue_depth", "tab:red", "admission queue"),
                              ("occupancy", "tab:blue", "busy slots")):
        series = summary.get(key) or []
        if series:
            ts = [float(t) for t, _ in series]
            ns = [int(n) for _, n in series]
            ax.step(ts, ns, where="post", color=color, label=label)
    n_slots = summary.get("n_slots")
    if isinstance(n_slots, (int, float)):
        ax.axhline(n_slots, color="gray", linestyle=":",
                   label=f"slot count ({int(n_slots)})")
    ax.set_xlabel("tick")
    ax.set_ylabel("requests")
    ax.set_ylim(bottom=0)
    ax.set_title(f"queue depth & slot occupancy "
                 f"({summary.get('policy', '?')} policy)", fontsize=10)
    ax.grid(alpha=0.3)
    ax.legend(fontsize=8)
    fig.tight_layout()
    if path:
        fig.savefig(path, dpi=120)
    return fig


def plot_throughput_grid(df: pd.DataFrame, path: Optional[str] = None):
    plt = _mpl()
    layer_vals = sorted(df["n_layers"].unique())
    head_vals = sorted(df["n_heads"].unique())
    fig, axes = plt.subplots(len(layer_vals), len(head_vals),
                             figsize=(4 * len(head_vals), 3.2 * len(layer_vals)),
                             squeeze=False)
    for i, L in enumerate(layer_vals):
        for j, H in enumerate(head_vals):
            ax = axes[i][j]
            sub = df[(df["n_layers"] == L) & (df["n_heads"] == H)]
            for schedule, g in sub.groupby("schedule"):
                g = g.sort_values("num_processes")
                ax.plot(g["num_processes"], g["throughput"],
                        marker="o", color=SCHEDULE_COLORS.get(schedule),
                        label=schedule)
            ax.set_title(f"L{L}, H{H}", fontsize=10)
            ax.set_xlabel("devices")
            ax.set_ylabel("tokens/sec")
            ax.grid(alpha=0.3)
            if i == 0 and j == 0:
                ax.legend(fontsize=8)
    fig.tight_layout()
    if path:
        fig.savefig(path, dpi=120)
    return fig
