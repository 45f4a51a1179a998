"""Attribute the device time of a traced step to the program's regions.

The program names its work with `repro.trace`: `jax.named_scope` regions in
the training step (``step.model``, ``step.mix``, ``step.update`` with
``layout``/``obfuscate``/``gossip``, ``step.report``, and the model's
sub-scopes) and ``repro.data.*`` host spans in the prefetcher.  A region
reaches the compiled program only as each instruction's ``op_name``
metadata (`compiled.as_text()`); the device trace names an operation by its
instruction alone (`bench.trace.op_name`).  So the attribution joins trace
operation -> instruction -> region, counting only operations inside the
intervals of the step's own module (another program's instructions may
share a name):

* `region_of` turns one ``op_name`` into a region path.  Work of the model
  splits into ``step.model/fwd`` and ``step.model/bwd`` by JAX's own
  ``transpose(...)`` wrapper; a rematerialized forward is backward work.
* `instruction_regions` maps every instruction of a program's text;
  `bench.run` puts that map and the step module's name in the readers'
  context.
* `region_ns` sums each region's own device time (`bench.trace.
  self_times`) inside the step module's runs (`bench.trace.load` keeps
  the ``XLA Modules`` intervals); time of the step module under no region
  is ``unscoped``.
* `window_regions` does that once for a traced window, over the cell's
  chips, and `reader_ms` gives the readers of ``bench/metrics/`` their
  number from it.
* `data_produce_ms` reads the data spans (``repro.data.*``) of the chunks
  a window consumed.

Run as a script, it drives one traced run of a cell through `bench.run`
unchanged and prints every region, not only those the readers report:

    python3 bench/regions.py --workload <cell> --seed <n> --seconds <s>

Diagnostic lines: ``phase="regions"`` (milliseconds per step and chip of
every region, ``unscoped``, the step module's busy time, and the derived
``model_fwd_ms``, ``model_bwd_ms``, ``update_layout_ms``,
``data_produce_ms``) and ``phase="memory_stats"`` (every key that
``memory_stats()`` gives, before and after the window).  ``--hlo-out``
writes the compiled step's text without metadata, for comparing two
builds.  The last line is `bench.run`'s result.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
UNSCOPED = "unscoped"
# the tables of source locations that `as_text()` prints before the
# computations; like metadata, they change with any edit of the source
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=(]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_METADATA = re.compile(r", metadata=\{[^}]*\}")
_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")


def _names():
    """The program's region and span names (`repro.trace`), imported on
    use: `bench.run` puts the checkout's ``src`` on the path first."""
    from repro import trace as rt
    return rt


def unwrap(component: str):
    """(name, transforms) of one ``op_name`` component:
    ``vmap(transpose(jvp(attn)))`` is ``("attn", ["vmap", "transpose",
    "jvp"])``."""
    transforms = []
    m = _WRAPPED.match(component)
    while m:
        transforms.append(m.group(1))
        component = m.group(2)
        m = _WRAPPED.match(component)
    return component, transforms


def region_of(op_name: str):
    """The region path of an instruction's ``op_name``, or None outside
    every step region.  A step region opened inside another owns what
    follows it; only the children of the innermost step region count."""
    rt = _names()
    children = {rt.STEP_MODEL: rt.MODEL_REGIONS,
                rt.STEP_UPDATE: rt.UPDATE_REGIONS}
    path, backward = [], False
    for part in op_name.split("/"):
        name, transforms = unwrap(part)
        if name in rt.STEP_REGIONS:
            path, backward = [name], "transpose" in transforms
            continue
        backward = backward or "transpose" in transforms
        if path and name in children.get(path[0], ()) and name not in path:
            path.append(name)
    if not path:
        return None
    if path[0] == rt.STEP_MODEL:
        path.insert(1, "bwd" if backward else "fwd")
    return "/".join(path)


def instruction_regions(hlo_text: str):
    """(module name, {instruction: region path or None}) of a compiled
    program's text.  An instruction that a compiler pass made without
    metadata (a copy that changes a layout, a rewritten dot) takes the
    region of the first of its operands that has one, or else of its
    first user that has one: the text lists operands before users."""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    module = m.group(1) if m else None
    out, made, users = {}, [], {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, operands = m.group(1), _OPERAND.findall(line[m.end():])
        for t in operands:
            users.setdefault(t, []).append(name)
        op = _OP_NAME.search(line)
        if op:
            out[name] = region_of(op.group(1))
        elif " parameter(" in line:
            out[name] = None
        else:
            out[name] = next((out[t] for t in operands if out.get(t)), None)
            made.append(name)
    for name in reversed(made):
        if out[name] is None:
            out[name] = next((out[u] for u in users.get(name, ())
                              if out.get(u)), None)
    return module, out


def strip_metadata(hlo_text: str) -> str:
    """A compiled program's text without its metadata and source tables:
    what regions must leave unchanged."""
    out, skip = [], False
    for line in hlo_text.splitlines():
        if line in _DEBUG_TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(_METADATA.sub("", line))
    return "\n".join(out) + "\n"


def module_intervals(modules, name: str):
    """(start, end) of each run of the module ``name``: a trace names a
    run ``<module>(<program id>)``."""
    return [(s, e) for n, s, e in modules
            if n == name or n.startswith(name + "(")]


def inside(ops, intervals, lo: int, hi: int):
    """The operations cut to [lo, hi] whose midpoint lies in one of
    ``intervals``."""
    from bench import trace as T
    return [o for o in T.clip(ops, lo, hi)
            if any(s <= (o[1] + o[2]) // 2 < e for s, e in intervals)]


def region_ns(ops, intervals, regions: dict, lo: int, hi: int) -> dict:
    """{region path or ``unscoped``: own device ns} of the operations in
    [lo, hi] inside ``intervals`` (the step module's runs)."""
    from bench import trace as T
    out = {}
    for name, ns in T.self_times(inside(ops, intervals, lo, hi)):
        key = regions.get(name) or UNSCOPED
        out[key] = out.get(key, 0) + ns
    return out


def data_produce_ms(spans, lo: int, hi: int):
    """Mean, per chunk the consumer took in [lo, hi], of its
    ``repro.data.produce`` and ``repro.data.place`` time; None without
    one."""
    rt = _names()
    taken = {st.get("step") for n, _, s, e, st in spans
             if n == rt.DATA_WAIT and lo <= e <= hi}
    # a chunk made before the trace began has no produce span: left out
    made = {st.get("step") for n, _, s, e, st in spans
            if n == rt.DATA_PRODUCE} & taken
    work = sum(e - s for n, _, s, e, st in spans
               if n in (rt.DATA_PRODUCE, rt.DATA_PLACE)
               and st.get("step") in made)
    return 1e-6 * work / len(made) if made else None


def summary(region: dict, per: float) -> dict:
    """The per-layer numbers a reader of ``region`` ({path: ns}) gives,
    in ms per step and chip (``per`` = steps x chips): each sums a
    region and the regions under it."""
    rt = _names()

    def ms(path):
        return 1e-6 * sum(v for k, v in region.items()
                          if (k + "/").startswith(path + "/")) / per
    return {"model_fwd_ms": ms(rt.STEP_MODEL + "/fwd"),
            "model_bwd_ms": ms(rt.STEP_MODEL + "/bwd"),
            "update_layout_ms": ms(rt.STEP_UPDATE + "/" + rt.LAYOUT)}


def window_regions(ctx) -> dict:
    """The step module's operations in the traced window of a run's
    readers' context (`bench.run`), once per context: ``region`` ({path
    or ``unscoped``: own ns}, summed over the cell's chips), ``busy`` (the
    module's busy ns, summed) and ``stray`` ({instruction: own ns} under
    no region)."""
    if "window_regions" not in ctx:
        from bench import trace as T
        reduced, lo, hi = ctx["trace"], *ctx["trace_window"]
        region, busy, stray = {}, 0, {}
        for plane in ctx["planes"]:
            ops = inside(reduced.device_ops[plane], module_intervals(
                reduced.modules.get(plane, []), ctx["module"]), lo, hi)
            for k, v in region_ns(ops, [(lo, hi)], ctx["regions"], lo,
                                  hi).items():
                region[k] = region.get(k, 0) + v
            busy += T.busy_ns(ops, lo, hi)
            for name, ns in T.self_times(ops):
                if not ctx["regions"].get(name):
                    stray[name] = stray.get(name, 0) + ns
        ctx["window_regions"] = {"region": region, "busy": busy,
                                 "stray": stray}
    return ctx["window_regions"]


def reader_ms(ctx, name: str):
    """``name`` of `summary` (ms per step and chip) for a run's readers'
    context; None where the window has no step, no device plane, or no
    time in that region."""
    if ctx["trace"] is None or not ctx["planes"] or not ctx["steps"]:
        return None
    v = summary(window_regions(ctx)["region"],
                ctx["steps"] * len(ctx["planes"]))[name]
    return v or None


def regions_line(ctx, update_kernel_ms=None) -> dict:
    """The ``phase="regions"`` line of a traced window: ms per step and
    chip of every region of the step module and of its busy time (only
    the module's name where the trace has no device plane)."""
    steps, planes = ctx["steps"], ctx["planes"]
    if not planes or not steps:
        return {"module": ctx["module"], "steps": steps, "ms": None}
    w = window_regions(ctx)
    per = steps * len(planes)
    ms = {k: 1e-6 * v / per for k, v in sorted(w["region"].items())}
    rt = _names()
    kernels = sum(v for k, v in ms.items() if k in (
        rt.STEP_UPDATE + "/" + rt.OBFUSCATE, rt.STEP_UPDATE + "/" + rt.GOSSIP))
    return {"module": ctx["module"], "steps": steps, "ms": ms,
            "sum_ms": sum(ms.values()),
            "module_busy_ms": 1e-6 * w["busy"] / per,
            "obfuscate_gossip_ms": kernels,
            "update_kernel_ms": update_kernel_ms,
            **summary(w["region"], per),
            "data_produce_ms": data_produce_ms(
                ctx["trace"].program_spans, *ctx["trace_window"]),
            "unscoped_top": [[k, 1e-6 * v / per] for k, v in sorted(
                w["stray"].items(), key=lambda kv: -kv[1])[:8]]}


# ---------------------------------------------------------------------------
# One traced run of a cell
# ---------------------------------------------------------------------------

def main(argv=None, root: Path = ROOT, require_tpu: bool = True) -> int:
    from unittest import mock
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--hlo-out", help="write the step's text, no metadata")
    args = p.parse_args(argv)

    import jax

    from bench import run as R
    seen, memory = {}, {}

    def listen(fn, real, when):    # the window opens and closes with these
        memory[when] = [d.memory_stats() or {} for d in jax.local_devices()]
        return real(fn)

    mon = jax.monitoring
    reg, unreg = (mon.register_event_listener,
                  mon.unregister_event_listener)
    with mock.patch.object(mon, "register_event_listener",
                           lambda fn: listen(fn, reg, "before")), \
            mock.patch.object(mon, "unregister_event_listener",
                              lambda fn: listen(fn, unreg, "after")):
        try:
            result = R.run(args, root, require_tpu, keep=seen)
        except R.Fail as e:
            print(f"regions: {e}", file=sys.stderr)
            return 1
    R.note(phase="memory_stats", **memory)
    if args.hlo_out:
        Path(args.hlo_out).write_text(strip_metadata(seen["hlo"]))
    if args.trace:
        R.note(phase="regions", **regions_line(
            seen["ctx"],
            result["metrics"].get("update_kernel_ms", {}).get("value")))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
