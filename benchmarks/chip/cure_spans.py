"""Where a CURe pass goes, read from the program's own spans and scopes on
one TPU: a tool beside the benchmark, not one of its cells.

    python3 benchmarks/chip/cure_spans.py --seed <n> [--passes 2] \
        [--cost 3] [--out cure_spans.json]

It makes the ``olmo-1b.cure`` cell's weights and calibration set from the
seed, runs one pass as set-up, then

1. ``--cost`` pairs of passes without the profiler, alternating
   ``tracer=None`` and an enabled ``repro.obs.Tracer``: what the
   tracer costs;
2. ``--passes`` passes under a ``jax.profiler`` capture, with the
   benchmark's ``chipbench.*`` annotations around calibrate and compress
   and the program's ``repro.*`` spans inside them,

and prints one JSON line (also written to ``--out``): device seconds per
pass under each ``cure_*`` scope (a fusion counts under the scope of its
root op), the idle seconds per pass split into ``wait`` (the innermost
``repro.*`` span over the gap ends in ``.wait``: the host blocked on the
device) and ``host`` (every other gap), the programs obtained inside the
traced window, and the ``repro.*`` spans that lie outside their pass's
``chipbench`` annotation (there should be none: the clock is shared).
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import glob
import json
import os
import re
import statistics
import sys
import time
from typing import Dict, List, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src"))
                if p not in sys.path]

from benchmarks.chip import trace as tr  # noqa: E402

CELL = "olmo-1b.cure"
REPRO = "repro."
SCOPE = re.compile(r"\b(cure_[a-z]+)\b")
STAGES = {tr.PREFIX + "calibrate", tr.PREFIX + "compress"}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview of the bytes for every other wire type."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _sub(buf, number: int):
    return [v for f, v in _fields(buf) if f == number]


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _module_scopes(module) -> Dict[str, str]:
    """Instruction name -> scope, for one ``HloModuleProto``."""
    table = {}
    for comp in _sub(module, 3):
        for inst in _sub(comp, 2):
            for md in _sub(inst, 7):
                m = SCOPE.search("".join(_text(v) for v in _sub(md, 2)))
                if m:
                    table[_text(_sub(inst, 1)[0])] = m.group(1)
    return table


def hlo_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Per program in the capture (named as the ``XLA Modules`` line names
    it, ``jit_f(<id>)``), its instructions' ``cure_*`` scopes, read from
    the optimized HLO that the profiler keeps on the ``/host:metadata``
    plane (a fusion's metadata is its root op's). The device's op events
    carry no scope themselves.

    Field numbers: ``XSpace.planes`` 1; ``XPlane.name`` 2,
    ``event_metadata`` 4 (map entry value 2); ``XEventMetadata.name`` 2,
    ``stats`` 5; ``XStat.bytes_value`` 6; ``HloProto.hlo_module`` 1;
    ``HloModuleProto.computations`` 3; ``HloComputationProto.instructions``
    2; ``HloInstructionProto.name`` 1, ``metadata`` 7;
    ``OpMetadata.op_name`` 2."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):
        if _text(_sub(plane, 2)[0]) != "/host:metadata":
            continue
        for entry in _sub(plane, 4):
            for meta in _sub(entry, 2):
                table = out.setdefault(_text(_sub(meta, 2)[0]), {})
                for stat in _sub(meta, 5):
                    for proto in _sub(stat, 6):
                        for module in _sub(proto, 1):
                            table.update(_module_scopes(module))
    return out


def instruction(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def scope_ops(ops: Sequence[tr.Event], modules: Sequence[tr.Event],
              tables: Dict[str, Dict[str, str]]) -> List[str]:
    """Each op's scope ("" for none): the program whose ``XLA Modules``
    event holds the op's start, then the op's instruction in that
    program's table. A program missing from the tables is looked up in
    the tables of the same function name where they agree. Each (program,
    op) is looked up once: loop bodies repeat the same ops thousands of
    times."""
    by_base = collections.defaultdict(list)
    for name, table in tables.items():
        by_base[name.split("(")[0]].append(table)
    owners = innermost(modules, [e.start_ns for e in ops])
    seen: Dict[tuple, str] = {}
    out = []
    for e, mod in zip(ops, owners):
        key = (mod, e.name)
        if key not in seen:
            inst = instruction(e.name)
            table = tables.get(mod)
            if table is not None:
                seen[key] = table.get(inst, "")
            else:
                found = {t.get(inst, "") for t in
                         by_base.get((mod or "").split("(")[0], [])}
                seen[key] = found.pop() if len(found) == 1 else ""
        out.append(seen[key])
    return out


def load(path: str):
    """``(devices, host, programs)``: per device plane its XLA ops as
    ``(event, scope)`` in order of start; the ``chipbench.*`` and
    ``repro.*`` host annotations under their full names; and the number
    of programs whose HLO the capture holds."""
    from jax.profiler import ProfileData
    tables = hlo_scopes(path)
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [tr.Event(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in ("XLA Ops", "XLA Modules")}
            ops = sorted(lines.get("XLA Ops", []), key=lambda e: e.start_ns)
            scopes = scope_ops(ops, lines.get("XLA Modules", []), tables)
            devices[plane.name] = list(zip(ops, scopes))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((REPRO, tr.PREFIX)):
                        host.append(tr.Event(e.name, e.start_ns,
                                             e.duration_ns))
    return devices, host, len(tables)


def innermost(spans: Sequence[tr.Event], points: Sequence[float]
              ) -> List[Optional[str]]:
    """For each time in ``points`` (ascending), the name of the innermost
    span that covers it, or None. Spans nest, as the spans of one host
    thread do, to any depth: a span that starts inside another ends
    inside it."""
    order = sorted(spans, key=lambda e: (e.start_ns, -e.dur_ns))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(order) and order[i].start_ns <= t:
            while stack and stack[-1].end_ns <= order[i].start_ns:
                stack.pop()
            stack.append(order[i])
            i += 1
        while stack and stack[-1].end_ns <= t:
            stack.pop()
        out.append(stack[-1].name if stack else None)
    return out


def reduce_spans(devices: Dict[str, list], host: List[tr.Event],
                 passes: int) -> dict:
    """Per pass, averaged over devices: busy and idle seconds, idle split
    into ``wait`` and ``host`` and by the innermost ``repro.*`` span
    (``none`` outside them), device seconds by ``cure_*`` scope, the
    device seconds of ops that start inside a ``chipbench.compress``
    annotation (and the unscoped ones among them by stable name), and
    the ``repro.*`` spans outside their pass's annotations."""
    wins = [e for e in host if e.name == tr.PREFIX + "window"]
    if not wins:
        raise ValueError("trace holds no chipbench.window annotation")
    if not devices:
        raise ValueError("trace holds no device operations")
    win = max(wins, key=lambda e: e.dur_ns)
    w0, w1 = win.start_ns, win.end_ns
    stages = [e for e in host if e.name in STAGES]
    compress = [e for e in stages if e.name == tr.PREFIX + "compress"]
    spans = [e for e in host if e.name.startswith(REPRO)]
    busy = idle = 0.0
    split = collections.Counter()
    by_span = collections.Counter()
    scope_s = collections.Counter()
    in_compress = 0.0
    unscoped = collections.Counter()
    for ops in devices.values():
        u = tr._union([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e, _ in ops if e.end_ns > w0 and e.start_ns < w1])
        busy += sum(b - a for a, b in u)
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        names = innermost(spans, [(a + b) / 2 for a, b in gaps])
        for (a, b), name in zip(gaps, names):
            idle += b - a
            split["wait" if name and name.endswith(".wait")
                  else "host"] += b - a
            by_span[name or "none"] += b - a
        starts = sorted(ops, key=lambda eo: eo[0].start_ns)
        marks = innermost(compress, [e.start_ns for e, _ in starts])
        for (e, scope), mark in zip(starts, marks):
            name = tr.stable_name(e.name)
            if not (w0 <= e.start_ns < w1) or name in tr.CONTAINERS:
                continue
            dur = min(e.end_ns, w1) - e.start_ns
            if scope:
                scope_s[scope] += dur
            if mark is not None:
                in_compress += dur
                if not scope:
                    unscoped[name] += dur
    k = len(devices) * passes * 1e9
    outside = sum(
        1 for e in spans
        if not any(s.start_ns <= e.start_ns and e.end_ns <= s.end_ns
                   for s in stages)
        or not (w0 <= e.start_ns and e.end_ns <= w1))
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) / passes / 1e9,
        "busy_s": busy / k, "idle_s": idle / k,
        "idle_share": 100.0 * idle / (len(devices) * (w1 - w0)),
        "idle_split_s": {x: split[x] / k for x in ("wait", "host")},
        "idle_by_span_s": {n: v / k for n, v in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "scope_s": {s: v / k for s, v in sorted(scope_s.items())},
        "compress_device_s": in_compress / k,
        "compress_unscoped_s": [[n, v / k] for n, v in top],
        "spans": len(spans), "spans_outside": outside,
    }


def _pass(params, cfg, batches, ccfg, tracer, annotate):
    import jax
    from repro.core import calibrate, compress_model
    with annotate("calibrate"):
        calib = calibrate(params, cfg, batches, tracer=tracer)
    with annotate("compress"):
        cparams, _, _ = compress_model(params, cfg, ccfg, calib,
                                       tracer=tracer)
        jax.block_until_ready(cparams)


def _plain(name):
    return contextlib.nullcontext()


def measure(cfg_file: dict, mix: dict, wl: dict, seed: int, passes: int,
            cost: int, trace_dir: str) -> dict:
    """Run the set-up pass, the cost pairs and the traced passes (their
    capture under ``trace_dir``); returns the timings, the traced window's programs and
    the tracer's seconds per span name, per traced pass."""
    import jax
    from benchmarks.chip import harness, weights
    from benchmarks.chip.modes import cure as mode
    from repro import obs
    cfg = harness.model_config(cfg_file)
    params = weights.make(cfg_file, seed)
    tokens = mode.calib_tokens(cfg_file, mix, seed)
    b = mix["batch"]
    batches = [{"tokens": jax.device_put(tokens[i:i + b])}
               for i in range(0, len(tokens), b)]
    ccfg = mode.cur_config(wl)
    _pass(params, cfg, batches, ccfg, None, _plain)
    out = {"cure_s": {"none": [], "tracer": []}}
    for _ in range(cost):
        for kind in ("none", "tracer"):
            tracer = (obs.Tracer(process="repro.cure") if kind == "tracer"
                      else None)
            t0 = time.perf_counter()
            _pass(params, cfg, batches, ccfg, tracer, _plain)
            out["cure_s"][kind].append(time.perf_counter() - t0)
    if passes < 1:
        return out
    tracer = obs.Tracer(process="repro.cure")
    programs0 = obs.jit_programs()
    cap = tr.Capture(trace_dir)
    cap.start()
    for _ in range(passes):
        _pass(params, cfg, batches, ccfg, tracer, tr.annotate)
    cap.stop()
    programs1 = obs.jit_programs()
    out["programs"] = {
        "programs": programs1[0] - programs0[0],
        "cache_loads": programs1[1] - programs0[1],
        "fun_names": sorted({e["attrs"]["fun_name"] for e in tracer.events
                             if e["name"] == "compile"})}
    out["span_s"] = {k: v / passes
                     for k, v in sorted(tracer.durations().items())}
    out["trace"] = cap
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--passes", type=int, default=2)
    ap.add_argument("--cost", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import jax
    from benchmarks.chip import harness
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, CELL)
    data = os.path.join(ROOT, "benchmarks", "chip")
    cfg_file = harness.load_json(os.path.join(
        ROOT, harness.find_config(bench, cell["config"])["file"]))
    wl = harness.load_json(os.path.join(data, "workloads", CELL + ".json"))
    mix = harness.load_json(os.path.join(data, "traffic",
                                         cell["traffic"] + ".json"))
    try:
        devices = harness.check_devices(jax.devices(), 1)
    except harness.DeviceError as e:
        sys.stderr.write(f"cure_spans.py: {e}\n")
        return 3
    harness.enable_compile_cache()
    out = measure(cfg_file, mix, wl, args.seed, args.passes, args.cost,
                  os.path.join(harness.SCRATCH_DIR, "trace", "cure_spans"))
    line = {"device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
            "seed": args.seed, "cure_s": out["cure_s"],
            "cure_s_median": {k: statistics.median(v)
                              for k, v in out["cure_s"].items() if v}}
    cap = out.pop("trace", None)
    if cap is not None:
        path, = glob.glob(os.path.join(cap.dir, "**", "*.xplane.pb"),
                          recursive=True)
        devs, host, n_hlo = load(path)
        line.update(programs=out["programs"], span_s=out["span_s"],
                    hlo_programs=n_hlo,
                    reduction=reduce_spans(devs, host, args.passes))
        cap.remove()
    text = json.dumps(line)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
