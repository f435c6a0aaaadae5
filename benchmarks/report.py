"""Render the sim-lattice perf trajectory from ``BENCH_history.jsonl`` (one
appended record per ``python -m benchmarks.run``, stamped with git SHA and
timestamp — see ``benchmarks.run.append_history``), and gate on it."""
from __future__ import annotations

import argparse
import json
import os

from benchmarks.run import HISTORY_PATH


def load_history(path: str = HISTORY_PATH) -> list[dict]:
    """The appended bench trajectory, oldest first ([] when never run).
    Malformed lines (a torn append) are skipped, not raised."""
    if not os.path.exists(path):
        return []
    entries = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                entries.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return entries


def history_table(entries) -> str:
    """Markdown trajectory of the sim-lattice bench across commits."""
    lines = [
        "| when | sha | backend | mesh | hosts | cells | steady cells/s | "
        "compile_s | n_compiles | speedup |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for e in entries:
        lines.append(
            f"| {str(e.get('timestamp', '?'))[:19]} | {e.get('git_sha', '?')} | "
            f"{e.get('backend', '?')} | {e.get('mesh_devices', '?')} | "
            f"{e.get('n_hosts', '?')} | {e.get('cells', '?')} | "
            f"{e.get('steady_cells_per_sec', '?')} | "
            f"{e.get('compile_seconds', '?')} | {e.get('n_compiles', '?')} | "
            f"{e.get('speedup', '?')} |"
        )
    return "\n".join(lines)


def _gate_key(e: dict) -> tuple:
    """The comparability key of a bench entry: only entries measuring the
    same workload on the same topology may be compared by the perf gate.
    ``mesh_shape``/``dim`` are absent in pre-2-D-mesh history — ``None``
    there matches only other legacy entries (likewise
    ``algorithms``/``local_steps``, absent before the local-update axis,
    and ``task``, absent before the model-task axis — a CNN entry never
    gate-compares against a logreg or legacy synthetic-task entry)."""
    algs = e.get("algorithms", None)
    return (
        e.get("backend"), e.get("mesh_shape", None),
        e.get("mesh_devices"), e.get("n_hosts"), e.get("dim", None),
        e.get("cells"), e.get("n_rounds"),
        tuple(algs) if algs is not None else None,
        e.get("local_steps", None),
        e.get("task", None),
    )


def gate_regression(
    entries: list[dict], max_regress: float = 0.2
) -> tuple[bool, str]:
    """Perf regression gate over the bench trajectory.

    Compares the LAST history entry's ``steady_cells_per_sec`` against the
    most recent PRIOR entry with the same :func:`_gate_key` (backend, mesh
    shape, host count, dim, sweep size, algorithms, local_steps, task).
    Returns ``(ok, message)`` — ok is
    False when throughput regressed by more than ``max_regress`` (fraction,
    default 20%). Passes trivially when there is no comparable prior entry
    (first run on a new configuration) or fewer than two entries total.
    """
    if len(entries) < 2:
        return True, "perf gate: <2 history entries, nothing to compare"
    last = entries[-1]
    cur = last.get("steady_cells_per_sec")
    if cur is None:
        return True, "perf gate: last entry has no steady_cells_per_sec"
    key = _gate_key(last)
    prior = next(
        (e for e in reversed(entries[:-1]) if _gate_key(e) == key), None
    )
    if prior is None or not prior.get("steady_cells_per_sec"):
        return True, (
            f"perf gate: no prior entry for {key}, passing trivially"
        )
    ref = float(prior["steady_cells_per_sec"])
    cur = float(cur)
    drop = (ref - cur) / ref
    msg = (
        f"perf gate: steady_cells_per_sec {cur:.3f} vs prior {ref:.3f} "
        f"({-drop:+.1%}; threshold -{max_regress:.0%}; key={key})"
    )
    return drop <= max_regress, msg


def main(history_path=HISTORY_PATH):
    history = load_history(history_path)
    if history:
        print("\n### §Sim-lattice trajectory (BENCH_history.jsonl)\n")
        print(history_table(history))
    else:
        print(f"\n(no bench history at {history_path} — run "
              "`python -m benchmarks.run` to start the trajectory)")


if __name__ == "__main__":
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--history", default=HISTORY_PATH)
    ap.add_argument(
        "--gate", action="store_true",
        help="perf regression gate: exit 1 when the last BENCH_history.jsonl "
        "entry's steady_cells_per_sec regressed more than --max-regress vs "
        "the most recent prior entry on the same backend/mesh shape "
        "(passes trivially with no comparable prior)",
    )
    ap.add_argument(
        "--max-regress", type=float, default=0.2, metavar="FRAC",
        help="allowed fractional throughput drop for --gate (default 0.2)",
    )
    args = ap.parse_args()
    if args.gate:
        ok, msg = gate_regression(
            load_history(args.history), max_regress=args.max_regress
        )
        print(msg)
        sys.exit(0 if ok else 1)
    main(args.history)
