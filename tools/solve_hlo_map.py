"""Map the host-driven solve's setup, gather and finish programs to their
compiled device ops.

    python3 tools/solve_hlo_map.py --workload flagship.steady --seed 1 \
        --out chiprun_out/hlo_map [--scale 1.0] [--runs 3]

Builds the cell's first round as `benchmark/runners/round_loop.py` does
(the deployment from the seed, `IncrementalRound`, the device-resident
sync), then compiles the solve's programs outside the pass-1 loop at
that round's shapes: `_pass1_begin`, `_pass1_norm`, `gather_window` and
`_round_finish_jit`. Each runs `--runs` times under the profiler. Writes
`<out>/<program>.hlo.txt` (the compiled HLO), the profile under
`<out>/trace/` and `<out>/map.json`, and
prints one line per device op that took at least 0.5 ms a run: its
program, its time per run, the kinds of HLO ops fused into it, and the
source line of its root. `--scale` shrinks the deployment's counts (a CPU
rehearsal); `--min-slots 0` lets a small round open a hot window.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"^(?:\([^=]*?\)|\S+)\s+([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_META = re.compile(r'(op_name|stack_frame_id)=("(?:[^"\\]|\\.)*"|\d+)')
_TABLE_ROW = re.compile(r'^(\d+) (?:"(.*)"|\{(.*)\})$')
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+.*\{\s*$")


def _frames(text: str) -> dict:
    """The module's stack-frame tables: frame id -> (file, line, function,
    parent frame id); a parent id of 0 means none."""
    tables, cur = {}, None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            cur = tables.setdefault(line, {})
            continue
        m = _TABLE_ROW.match(line) if cur is not None else None
        if not m:
            cur = None
            continue
        key, quoted, fields = m.groups()
        cur[int(key)] = quoted if quoted is not None else dict(
            kv.split("=") for kv in fields.split())
    files, funcs = tables.get("FileNames", {}), tables.get("FunctionNames", {})
    locs, out = tables.get("FileLocations", {}), {}
    for fid, fr in tables.get("StackFrames", {}).items():
        loc = locs.get(int(fr["file_location_id"]), {})
        out[fid] = (files.get(int(loc.get("file_name_id", 0)), "?"), loc.get("line", "?"),
                    funcs.get(int(loc.get("function_name_id", 0)), "?"),
                    int(fr["parent_frame_id"]) - 1)
    return out


def source_of(frames: dict, frame_id, depth: int = 3) -> str:
    """The innermost `depth` frames of the program's own code behind an op."""
    out, fid, seen = [], int(frame_id or 0), set()
    while fid > 0 and fid not in seen and len(out) < depth:
        seen.add(fid)
        path, line, func, parent = frames.get(fid, ("?", "?", "?", 0))
        if "armada_tpu" in path:
            out.append(f"{os.path.basename(path)}:{line} {func}")
        fid = parent
    return " < ".join(out) or "?"


def parse_hlo(text: str) -> dict:
    """Per instruction name: opcode, the computation it calls, metadata;
    per computation: the opcodes in it."""
    instrs, comps, cur = {}, {}, None
    frames = _frames(text)
    for line in text.splitlines():
        if line.rstrip().endswith("{") and " = " not in line:
            m = _COMP.match(line)
            cur = m.group(1) if m else None
            comps.setdefault(cur, [])
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.match(rest)
        opcode = op.group(1) if op else "?"
        calls = _CALLS.search(rest)
        meta = {k: v.strip('"') for k, v in _META.findall(rest)}
        meta["source"] = source_of(frames, meta.pop("stack_frame_id", 0))
        instrs[name] = {"opcode": opcode, "calls": calls.group(1) if calls else None,
                        **meta}
        if cur is not None:
            comps[cur].append(opcode)
    return {"instrs": instrs, "comps": comps}


def fused_kinds(hlo: dict, name: str) -> list[str]:
    """The notable op kinds inside an op (and what it calls, one level)."""
    ins = hlo["instrs"].get(name, {})
    ops = list(hlo["comps"].get(ins.get("calls"), [])) or [ins.get("opcode", "?")]
    keep = ("scatter", "gather", "reduce", "reduce-window", "sort", "while",
            "conditional", "dynamic-slice", "dynamic-update-slice", "cumsum")
    return sorted({o for o in ops if o in keep})


def build_round(workload: str, seed: int, scale: float):
    from armada_tpu.snapshot.incremental import IncrementalRound
    from armada_tpu.snapshot.residency import ResidentRound

    from benchmark import generate
    from benchmark.harness import Cell

    cell = Cell(ROOT, workload)
    config = json.loads(json.dumps(cell.config))
    if scale != 1.0:
        for key in ("nodes", "backlog", "running"):
            config[key]["count"] = max(8, int(config[key]["count"] * scale))
    cfg = generate.scheduling_config(config)
    node_list = generate.nodes(config)
    running = generate.running_jobs(config, node_list, seed)
    backlog = generate.JobStream(config["backlog"], config, seed, 1, "job-")
    queued = backlog.take(int(config["backlog"]["count"]))
    inc = IncrementalRound(cfg, config["pool"], node_list, generate.queues(config),
                           running, queued)
    return ResidentRound().device_round(inc), cfg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="flagship.steady")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--min-slots", type=int, default=None)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from armada_tpu.utils.platform import setup_jax

    setup_jax()
    import jax
    import jax.numpy as jnp

    from armada_tpu.core import resources
    from armada_tpu.solver import kernel
    from armada_tpu.solver.hotwindow import gather_window

    from benchmark.trace_reduce import _device_events, find_xplane

    resources.ensure_native()
    os.makedirs(args.out, exist_ok=True)
    t0 = time.monotonic()
    dev, cfg = build_round(args.workload, args.seed, args.scale)
    dev = jax.device_put(dev)
    min_slots = cfg.hot_window_min_slots if args.min_slots is None else args.min_slots
    pre = kernel._window_precheck(dev, cfg.hot_window_slots, min_slots)
    print(f"built in {time.monotonic() - t0:.1f} s: J={dev.job_req.shape} "
          f"S,M={dev.slot_members.shape} alloc={dev.alloc0.shape} "
          f"Q={dev.queue_weight.shape[0]} C={dev.pc_priority.shape[0]} "
          f"P={dev.priorities.shape[0]} window={pre}", flush=True)

    # Compile each program once, at the shapes the host-driven solve uses.
    hlo = {}

    def compile_(name, jitted, *args_, statics=()):
        t = time.monotonic()
        compiled = jitted.lower(*args_, *statics).compile()
        text = compiled.as_text()
        with open(os.path.join(args.out, f"{name}.hlo.txt"), "w") as f:
            f.write(text)
        hlo[name] = parse_hlo(text)
        print(f"compiled {name} in {time.monotonic() - t:.1f} s", flush=True)
        return compiled

    begin = compile_("_pass1_begin_impl", kernel._pass1_begin, dev)
    begin_out = begin(dev)
    carry, ptr = begin_out[:2]
    plan = kernel._window_plan(dev, carry, pre)
    calls = [("_pass1_begin_impl", lambda: begin(dev))]
    if plan is not None:
        Ws, Ep, _ = plan
        norm = compile_("_pass1_norm_impl", kernel._pass1_norm, dev, carry, ptr)
        gather = compile_("gather_window", gather_window, dev, carry, ptr,
                          statics=(Ws, Ep))
        calls += [
            ("_pass1_norm_impl", lambda: norm(dev, carry, jnp.array(ptr))),
            ("gather_window", lambda: gather(dev, carry, ptr)),
        ]
    # `_finish_impl` takes what `_pass1_begin` returns but the pointers.
    fin_args = (begin_out[0],) + tuple(begin_out[2:])
    finish = compile_("_finish_impl", kernel._round_finish_jit, dev, *fin_args,
                      statics=(False,))
    calls.append(("_finish_impl", lambda: finish(dev, *fin_args)))

    for _, call in calls:  # warm
        jax.block_until_ready(call())
    trace_dir = os.path.join(args.out, "trace")
    wall = {}
    with jax.profiler.trace(trace_dir):
        for name, call in calls:
            t = time.perf_counter()
            for _ in range(args.runs):
                jax.block_until_ready(call())
            wall[name] = (time.perf_counter() - t) / args.runs * 1e3
    path = find_xplane(trace_dir)
    rows = []
    if path is not None:
        from jax.profiler import ProfileData

        op_time = {}
        planes = [p for p in ProfileData.from_file(path).planes
                  if p.name.startswith("/device:") and ":CUSTOM:" not in p.name]
        for plane in planes[:1]:
            for name, s, e in _device_events(plane):
                op_time[name] = op_time.get(name, 0.0) + (e - s)
        for full, ns in sorted(op_time.items(), key=lambda kv: -kv[1]):
            ms = ns / 1e6 / args.runs
            if ms < 0.5:
                continue
            module, _, op = full.rpartition("/")
            prog = module[len("jit_"):] if module.startswith("jit_") else module
            h = hlo.get(prog, {"instrs": {}, "comps": {}})
            meta = h["instrs"].get(op, {})
            rows.append({
                "op": full, "ms_per_run": round(ms, 3), "kinds": fused_kinds(h, op),
                "source": meta.get("source", "?"),
                "op_name": meta.get("op_name", "")[-160:],
            })
    doc = {"shapes": {"J": int(dev.job_req.shape[0]),
                      "S": int(dev.slot_members.shape[0]),
                      "M": int(dev.slot_members.shape[1])},
           "wall_ms_per_run": {k: round(v, 3) for k, v in wall.items()},
           "ops": rows,
           "device": str(jax.devices()[0].device_kind)}
    with open(os.path.join(args.out, "map.json"), "w") as f:
        json.dump(doc, f, indent=1)
    print("wall ms per run " + json.dumps(doc["wall_ms_per_run"]), flush=True)
    for r in rows:
        print(f"{r['ms_per_run']:10.3f} ms  {r['op']:48s} {','.join(r['kinds']):28s} "
              f"{r['source']}  {r['op_name']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
