"""What the program marks on itself, read for the per-layer metrics: the
name stack XLA keeps for each device operation (the epoch program's
``jax.named_scope`` stages), the program's host spans
(``repro.core.spans``) on the profiler trace's clock, and the program's
in-memory span record, which covers every epoch.

This adds to ``chipbench/trace.py`` without changing it: a ``Trace`` and
a map ``{op text: name stack}``. The profile names each op event by its
instruction's HLO text without metadata, so the name stacks come from
the compiled program itself (``scopes_from_cache``). A program that
marks nothing (no listed scope, no such span) gives nothing, and the
readers then return None.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import statistics

from chipbench import trace as trace_mod

# the epoch program's device stages, innermost listed scope wins
STAGES = ("rollout", "validation", "fake_quant", "reward", "replay_push",
          "update")
# the host phases of an epoch that leave the device idle (the wait is
# the host waiting for the device)
HOST_PHASES = ("search.epoch.args", "search.epoch.dispatch",
               "search.epoch.readback", "search.epoch.records")
EPOCH_SPAN = "search.epoch"
GC_SPAN = "python.gc"
# an instruction of an HLO module's text and its name stack
_INSTRUCTION = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = [^\n]*?'
                          r'metadata=\{[^\n]*?op_name="([^"]*)"', re.M)


def _window_modules(pd, window) -> set:
    """Names of the XLA modules that ran on a device inside the window
    ("jit_epoch(123)" -> "jit_epoch")."""
    w0, w1 = window
    return {e.name.split("(")[0] for p in pd.planes
            if trace_mod._is_device_plane(p.name) for ln in p.lines
            if ln.name == "XLA Modules" for e in ln.events
            if e.end_ns > w0 and e.start_ns < w1}


def cached_name_stacks(path: str) -> dict:
    """{instruction name: name stack} of one compiled program in JAX's
    persistent compilation cache: the entry is deserialized on this
    process's backend (in the cache's own format) and its optimized HLO
    modules read for each instruction's ``op_name`` metadata."""
    from jax._src import compilation_cache as cc
    from jax._src import xla_bridge
    with open(path, "rb") as f:
        serialized, _ = cc.extract_executable_and_time(
            cc.decompress_executable(f.read()))
    backend = xla_bridge.get_backend()
    exe = backend.deserialize_executable(serialized, backend.devices()[:1],
                                         None)
    return dict(_INSTRUCTION.findall(
        "\n".join(m.to_string() for m in exe.hlo_modules())))


def scopes_from_cache(tr, modules, cache_dir: str) -> dict:
    """{op text: name stack} of the trace's ops, from the cached program
    among ``modules`` whose instructions name most of them: an op event
    is named by its instruction's HLO text ("%fusion.9 = ...")."""
    ops = {op for evs in tr.ops.values() for op, _, _ in evs}
    best = {}
    for mod in sorted(modules):
        for path in sorted(glob.glob(os.path.join(glob.escape(cache_dir),
                                                  mod + "-*-cache"))):
            stacks = cached_name_stacks(path)
            got = {}
            for op in ops:
                stack = stacks.get(op.split(" = ", 1)[0].lstrip("%"))
                if stack is not None:
                    got[op] = stack
            if len(got) > len(best):
                best = got
    return best


def _newest_profile(directory: str):
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def scopes_for(ctx) -> dict:
    """The name stacks of the run's traced window: ``ctx.scopes`` where
    given (a recorded excerpt), else those of the programs that ran in
    the window of the newest profile under the benchmark's trace
    directory, if that window is ``ctx.trace``'s, read from the
    persistent compilation cache: the profile's op events carry no name
    stack, and the harness's profiler options leave the HLO out of it.
    Cached on ``ctx``; {} when there is none."""
    got = getattr(ctx, "scopes", None)
    if got is not None:
        return got
    from chipbench import harness
    got = {}
    path = _newest_profile(harness.TRACE_DIR)
    if path is not None:
        import jax
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        window = [(e.start_ns, e.end_ns) for p in pd.planes
                  if p.name.startswith("/host:") for ln in p.lines
                  for e in ln.events if e.name == trace_mod.WINDOW_SPAN]
        cache_dir = jax.config.jax_compilation_cache_dir
        if window == [tuple(ctx.trace.window)] and cache_dir:
            got = scopes_from_cache(
                ctx.trace, _window_modules(pd, ctx.trace.window), cache_dir)
    ctx.scopes = got
    return got


# a name-stack component: a scope, or a scope under transforms
# ("vmap(fake_quant)", "transpose(jvp(update))")
_COMPONENT = re.compile(r"((?:[\w]+\()*)([^()]*)\)*")


def stage_of(stack: str, stages=STAGES) -> str:
    """The innermost of ``stages`` in a name stack ("jit(epoch)/while/
    body/validation/vmap(fake_quant)/mul" -> "fake_quant"), else
    "other". A nested ``jit(name)`` is a function, not a scope."""
    for part in reversed((stack or "").split("/")):
        m = _COMPONENT.fullmatch(part)
        if m and m.group(2) in stages and "jit(" not in m.group(1):
            return m.group(2)
    return "other"


def stage_seconds(tr, scopes: dict, stages=STAGES) -> dict:
    """Device seconds per stage, summed over chips, by the self-time rule
    of ``Trace.op_seconds`` (each instant goes to the op that started
    last), each op charged to the innermost listed scope in its name
    stack and the rest to "other": the values sum to ``busy_s()`` times
    the chips."""
    of = {}
    for evs in tr.ops.values():
        for n, _, _ in evs:
            if n not in of:
                of[n] = stage_of(scopes.get(n), stages)
    # the same trace with each op renamed to its stage
    staged = trace_mod.Trace(
        window=tr.window, host=tr.host,
        ops={d: [(of[n], s, e) for n, s, e in evs]
             for d, evs in tr.ops.items()})
    out = dict.fromkeys(tuple(stages) + ("other",), 0.0)
    out.update(staged.op_seconds())
    return out


def epoch_stages_ms(ctx):
    """{stage: ms per traced epoch} with "forward" for validation outside
    fake quantization, or None when no device op carries a stage; cached
    on ``ctx``."""
    if not hasattr(ctx, "stages_ms"):
        ctx.stages_ms = _epoch_stages_ms(ctx)
    return ctx.stages_ms


def _epoch_stages_ms(ctx):
    batches = ctx.counters.get("traced_batches", 0)
    per_epoch = ctx.traffic.get("batches_per_epoch")
    scopes = scopes_for(ctx)
    if not batches or not per_epoch or not scopes:
        return None
    sec = stage_seconds(ctx.trace, scopes)
    if not any(sec[s] for s in STAGES):
        return None
    epochs = batches / per_epoch
    ms = {k: 1e3 * v / epochs for k, v in sec.items()}
    return {"rollout": ms["rollout"], "fake_quant": ms["fake_quant"],
            "forward": ms["validation"], "update": ms["update"],
            "other": ms["other"] + ms["reward"] + ms["replay_push"]}


def _union(intervals) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def host_idle_s(tr, names=HOST_PHASES):
    """Seconds inside the window in which the first chip ran nothing
    while the host was in one of the named spans; None when the trace
    has none of them."""
    if not tr.ops:
        return None
    w0, w1 = tr.window
    host = _union((max(s, w0), min(e, w1)) for n, s, e in tr.host
                  if n in names and e > w0 and s < w1)
    if not host:
        return None
    busy = tr.busy_intervals(sorted(tr.ops)[0])
    covered = sum(e - s for s, e in host)
    overlap = 0
    i = 0
    for s, e in host:
        while i < len(busy) and busy[i][1] <= s:
            i += 1
        j = i
        while j < len(busy) and busy[j][0] < e:
            overlap += min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
    return (covered - overlap) * 1e-9


def excerpt(tr, scopes: dict, t0: int, t1: int) -> dict:
    """``Trace.excerpt`` with the name stacks of its ops under
    "scopes"."""
    out = tr.excerpt(t0, t1)
    names = {n for p in out["planes"]
             if trace_mod._is_device_plane(p["name"])
             for ln in p["lines"] for n, _, _ in ln["events"]}
    out["scopes"] = {n: scopes[n] for n in sorted(names) if n in scopes}
    return out


def from_json(path: str) -> tuple:
    """(Trace, scopes) of an excerpt; scopes {} where it has none."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        raw = json.load(f)
    return trace_mod.from_json(path), raw.get("scopes", {})


# ---------------------------------------------------------------------------
# The program's in-memory span record
# ---------------------------------------------------------------------------

def recorded_spans(ctx):
    """The program's span record (``repro.core.spans``), drained once and
    kept on ``ctx.spans``; None for a program that keeps none."""
    got = getattr(ctx, "spans", None)
    if got is None:
        try:
            from repro.core import spans
        except ImportError:
            return None
        got = ctx.spans = spans.drain()
    return got


def window_epochs(ctx):
    """The ``search.epoch`` spans of the window's epochs that ran after
    the profiler stopped, oldest first, or None: the window's epochs are
    the last ``window_episodes / (K x E)`` recorded; the first
    ``1 + trace_epochs`` of them ran before or under the profiler."""
    rec = recorded_spans(ctx)
    n_ep = ctx.counters.get("window_episodes", 0)
    per = (ctx.traffic.get("episodes_per_batch", 0)
           * ctx.traffic.get("batches_per_epoch", 0))
    if not rec or not n_ep or not per:
        return None
    epochs = [s for s in rec if s[0] == EPOCH_SPAN]
    n_win = n_ep // per
    if n_win > len(epochs):
        return None
    after = epochs[len(epochs) - n_win:][1 + ctx.traffic.get(
        "trace_epochs", 0):]
    return after or None


def stall(ctx):
    """(slowest epoch - median, in ms; a line describing the slowest
    epoch: its phases and any generation-2 collection inside it), or
    None."""
    epochs = window_epochs(ctx)
    if not epochs:
        return None
    dur = [(e[3] - e[2]) * 1e-6 for e in epochs]
    med = statistics.median(dur)
    i = max(range(len(dur)), key=dur.__getitem__)
    slow = epochs[i]
    first = slow[4].get("first_episode")
    phases = {}
    gcs = []
    for n, _, s, e, attrs in recorded_spans(ctx):
        if n.startswith(EPOCH_SPAN + ".") and \
                attrs.get("first_episode") == first:
            phases[n[len(EPOCH_SPAN) + 1:]] = round((e - s) * 1e-6, 3)
        elif n == GC_SPAN and e > slow[2] and s < slow[3]:
            gcs.append(round((e - s) * 1e-6, 3))
    line = (f"slowest window epoch: first_episode {first}, "
            f"{dur[i]:.3f} ms (median {med:.3f} ms over {len(dur)}); "
            f"phases ms {phases}; python.gc ms inside {gcs}")
    return dur[i] - med, line
