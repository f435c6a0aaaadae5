import os
if __name__ == "__main__":
    # MUST precede any other import (jax locks the device count at first
    # initialization): the dry-run needs 512 placeholder devices for the
    # production mesh. Guarded on __main__ so merely IMPORTING this module
    # (tests, benchmarks) never flips the ambient process to 512 devices —
    # smoke tests must see 1 device.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture × input shape × mesh) combination this lowers and
compiles the corresponding step (train_step for train shapes, prefill /
serve_step for inference shapes) against ShapeDtypeStruct inputs — no
allocation — and reports:

  * memory_analysis()  — per-device bytes (proves the config fits HBM)
  * cost_analysis()    — per-device HLO FLOPs / bytes (roofline inputs)
  * collective bytes   — parsed from the partitioned HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute operands)

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod] \
      --json out.json
"""
import argparse
import json
import re
import sys
import time

_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _BYTES.get(dtype, 4)


_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\("
)
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPL_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


_COMP_RE = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\(.*?\),\s*condition=(%[\w.\-]+),\s*body=(%[\w.\-]+)"
)
_CONST_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")
_CALL_RE = re.compile(
    r"(?:calls=|to_apply=|branch_computations=\{|true_computation=|"
    r"false_computation=|computation=)(%[\w.\-]+)"
)


def _split_computations(hlo_text: str) -> dict:
    """{computation_name: [lines]} from HLO long text."""
    comps: dict = {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m:
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                comps["__entry__"] = comps[cur]
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _wire_bytes(op: str, result: int, g: int) -> int:
    if op == "all-gather":
        return result * (g - 1) // g
    if op == "reduce-scatter":
        return result * (g - 1)
    if op == "all-reduce":
        return 2 * result * (g - 1) // g
    if op == "all-to-all":
        return result * (g - 1) // g
    return result  # collective-permute


def parse_collective_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes of every collective in the partitioned HLO,
    MULTIPLIED by the trip counts of the while-loops enclosing it (XLA's
    text shows a loop body once; a collective inside the 88-layer scan
    executes 88×).

    Wire-byte convention (ring algorithm, group size g): all-gather
    (g-1)/g·result; reduce-scatter (g-1)·result; all-reduce 2(g-1)/g·result;
    all-to-all (g-1)/g·result; collective-permute result.

    Returns {op: {"count": static_op_count, "bytes": trip-weighted bytes}}.
    """
    comps = _split_computations(hlo_text)
    if not comps:
        comps = {"%toplevel": hlo_text.splitlines()}

    # loop structure: body computation -> trip count; parent -> children
    trip_of_body: dict = {}
    children: dict = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        for line in lines:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                consts = [int(c) for c in _CONST_RE.findall(
                    "\n".join(comps.get(cond, []))
                )]
                trip_of_body[body] = max(consts) if consts else 1
                children.setdefault(name, []).append((body, trip_of_body[body]))
            for cm in _CALL_RE.finditer(line):
                children.setdefault(name, []).append((cm.group(1), 1))

    # effective multiplier per computation (entry = 1), DFS
    entry_lines = comps.get("__entry__")
    entry_name = next(
        (n for n, ls in comps.items() if n != "__entry__" and ls is entry_lines),
        None,
    )
    mult = {entry_name: 1}
    stack = [entry_name]
    seen = set()
    while stack:
        cur = stack.pop()
        if cur in seen or cur is None:
            continue
        seen.add(cur)
        for child, trips in children.get(cur, []):
            m_new = mult.get(cur, 1) * trips
            if m_new > mult.get(child, 0):
                mult[child] = m_new
                stack.append(child)

    out: dict = {}
    for name, lines in comps.items():
        if name == "__entry__":
            continue
        factor = mult.get(name, 1)
        for line in lines:
            m = _COLL_RE.search(line)
            if not m:
                continue
            shape_txt, op = m.group(1), m.group(2)
            result = sum(
                _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(shape_txt)
            )
            g = _group_size(line)
            rec = out.setdefault(op, {"count": 0, "bytes": 0})
            rec["count"] += 1
            rec["bytes"] += _wire_bytes(op, result, g) * factor
    return out


def cost_analysis_dict(cost) -> dict:
    """jax version compat: ``cost_analysis()`` returns a dict on newer jax
    but a (possibly empty) one-element list of dicts on older releases."""
    if isinstance(cost, (list, tuple)):
        return cost[0] if cost else {}
    return cost or {}


class _UnrolledScans:
    """Monkeypatch jax.lax.scan to fully unroll — XLA cost analysis counts a
    while-loop body ONCE, so the scanned-layer build under-reports FLOPs by a
    factor of n_layers. The unrolled build is only LOWERED (never compiled):
    its pre-SPMD cost_analysis gives faithful whole-program FLOPs/bytes."""

    def __enter__(self):
        import jax as _jax

        self._orig = _jax.lax.scan

        def unrolled(f, init=None, xs=None, length=None, **kw):
            kw["unroll"] = True
            return self._orig(f, init, xs, length, **kw)

        _jax.lax.scan = unrolled
        return self

    def __exit__(self, *exc):
        import jax as _jax

        _jax.lax.scan = self._orig
        return False


def run_one(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True):
    import jax

    from repro import configs
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_step
    from repro.models.config import INPUT_SHAPES

    shape = INPUT_SHAPES[shape_name]
    if not configs.supports_shape(arch, shape):
        return {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "skipped",
            "reason": "pure full-attention arch — no long_500k variant (DESIGN §4)",
        }

    cfg = configs.get_config(arch, shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        bundle = build_step(cfg, shape, mesh)
        lowered = bundle.fn.lower(*bundle.arg_structs.values())
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        # faithful FLOP count: unrolled lowering (never compiled)
        t1 = time.time()
        with _UnrolledScans():
            bundle_u = build_step(cfg, shape, mesh)
            cost_u = cost_analysis_dict(
                bundle_u.fn.lower(*bundle_u.arg_structs.values()).cost_analysis()
            )
        t_unroll = time.time() - t1

    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled.cost_analysis())
    coll = parse_collective_bytes(compiled.as_text())

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "n_devices": mesh.devices.size,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            # donated args alias outputs; live set ≈ temps + max(arg, out)
            "peak_bytes": int(
                getattr(mem, "temp_size_in_bytes", 0)
                + max(
                    getattr(mem, "argument_size_in_bytes", 0),
                    getattr(mem, "output_size_in_bytes", 0),
                )
            ),
        },
        "cost": {
            # per-device, scan bodies counted once (compiled, partitioned)
            "flops_per_device_scanned": float(cost.get("flops", -1)),
            "bytes_accessed_per_device": float(cost.get("bytes accessed", -1)),
            # whole-program, unrolled, pre-SPMD (global; divide by chips)
            "flops_global": float(cost_u.get("flops", -1)),
            "bytes_accessed_global": float(cost_u.get("bytes accessed", -1)),
            "transcendentals_global": float(cost_u.get("transcendentals", -1)),
        },
        "collectives": coll,
        "collective_bytes_per_device": int(
            sum(v["bytes"] for v in coll.values())
        ),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "unroll_s": round(t_unroll, 1),
    }
    if verbose:
        print(
            f"[dryrun] {arch:>22s} × {shape_name:<12s} mesh={rec['mesh']:>8s}"
            f"  peak={rec['memory']['peak_bytes']/2**30:7.2f} GiB/dev"
            f"  flops={rec['cost']['flops_global']:.3e}"
            f"  coll={rec['collective_bytes_per_device']/2**20:9.1f} MiB/dev"
            f"  (lower {t_lower:.0f}s compile {t_compile:.0f}s unroll {t_unroll:.0f}s)",
            flush=True,
        )
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None, help="append JSONL records here")
    args = ap.parse_args()

    from repro import configs
    from repro.models.config import INPUT_SHAPES

    archs = list(configs.ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = run_one(arch, shape, mp)
                except Exception as e:  # noqa: BLE001 — report and continue
                    rec = {
                        "arch": arch, "shape": shape,
                        "mesh": "2x16x16" if mp else "16x16",
                        "status": "error", "error": f"{type(e).__name__}: {e}",
                    }
                    failures += 1
                    print(f"[dryrun] FAIL {arch} × {shape}: {rec['error']}",
                          flush=True)
                records.append(rec)
                if args.json:
                    with open(args.json, "a") as f:
                        f.write(json.dumps(rec) + "\n")

    ok = sum(1 for r in records if r["status"] == "ok")
    sk = sum(1 for r in records if r["status"] == "skipped")
    print(f"[dryrun] done: {ok} ok, {sk} skipped, {failures} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
