"""Serving launcher: batched prefill + decode loop with a KV/state cache.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --reduced \
      --batch 4 --prompt-len 32 --gen 32

Also serves the paper's stencil workload as a MULTI-TENANT REQUEST-QUEUE
SERVER: `--stencil 7pt-const` runs a continuous-batching loop where incoming
requests (each: advance my grid N time steps) are bucketed by **padding
class** — operator fingerprint, per-axis ladder rung of the grid shape
(`--pad pow2` or a rung list; default exact shapes), dtype, step count and
scalar coefficients — and every bucket head waits at most
`--batch-window-ms` for up to `--max-batch` same-class arrivals before ONE
fused `ops.mwd_batched` launch advances the whole batch, smaller grids
riding along under frozen-halo masking (`repro.core.padding`) so each
response stays bitwise-equal to its sequential `ops.mwd` run.  One launch
for B users instead of B kernel round-trips is the serving analogue of the
paper's intra-tile sharing: the shared resource is the launch itself.

The queue is a two-lane (interactive/batch) bounded queue with admission
control: offers past the watermark are rejected with a retry-after hint, and
a near-deadline head closes its batching window early using the
batch-amortization model (policy lives in `repro.core.scheduler`).  Live
telemetry (`--telemetry stdout` or ``jsonl:<path>``) exports per-bucket
throughput, queue depth, padding waste, plan-cache hit rate and rolling
latency percentiles.  Plans resolve registry-first under the batched
``b<B>`` key (run `python -m repro.launch.tune` once; every later server
start skips the search):

  PYTHONPATH=src python -m repro.launch.serve --stencil 7pt-const \
      --grid "6,10,8;6,12,10" --pad pow2 --requests 8 --steps 4 \
      --max-batch 4 --batch-window-ms 5 --telemetry stdout
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache, configs
from repro.distributed import elastic
from repro.launch import telemetry as tlm
from repro.models import lm
from repro.models.params import tree_init
from repro.training import sharding as shd
from repro.training import steps as tsteps


def prefill_into_cache(cfg, params, tokens, gen: int,
                       cache_len: int | None = None):
    """Prefill by stepping the decode path (simple and exact).

    A fused chunked prefill-into-cache is the serving-optimized variant.
    The cache is sized for the WHOLE request — prompt plus the `gen` tokens
    the decode loop will append. (It used to be a fixed prompt+64, which
    silently overflowed — wrapped or clobbered positions — as soon as
    --gen exceeded 64.)  A caller-provided `cache_len` is guarded against
    that same overflow instead of trusted; the guard uses the same
    ``max(gen, 1)`` rule as the default sizing because decode reads one
    slot past the prompt even when gen=0.
    """
    if gen < 0:
        raise ValueError(f"gen must be >= 0, got {gen}")
    b, s = tokens.shape
    if cache_len is None:
        cache_len = s + max(gen, 1)     # decode reads one slot past prefill
    if cache_len < s + max(gen, 1):
        raise ValueError(f"cache_len={cache_len} cannot hold the "
                         f"{s}-token prompt plus {max(gen, 1)} decode slots")
    cache = lm.init_cache(cfg, b, cache_len)
    serve = tsteps.make_serve_step(cfg)
    logits = None
    for i in range(s):
        _, logits, cache = serve(params, cache, tokens[:, i:i + 1])
    return logits, cache


# ---------------------------------------------------------------------------
# Stencil request-queue serving (continuous batching over the MWD kernel)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(eq=False)        # identity equality: fields hold arrays
class StencilRequest:
    """One user request: advance my resident grid `n_steps` time steps.

    `priority` picks the queue lane (``"interactive"`` is always drained
    first); `deadline_s` — like `arrival_s` an offset from server start —
    lets the window policy close a batch early so the head still makes its
    deadline (`math.inf` means no deadline).
    """

    rid: int
    spec: object                # StencilOp
    state: tuple                # (cur, prev)
    coeffs: object              # the op's packed coefficients
    n_steps: int
    arrival_s: float = 0.0      # offset from server start
    priority: str = "batch"     # queue lane: "interactive" | "batch"
    deadline_s: float = math.inf


@dataclasses.dataclass(frozen=True)
class Rejected:
    """Admission-control verdict: queue full, retry after `retry_after_s`."""

    retry_after_s: float


def bucket_key(spec, state, coeffs, n_steps: int, ladder=None) -> tuple:
    """Batchability class of a request.

    Requests may share one fused batched launch iff they agree on the
    operator's structural fingerprint, **padding class** (the grid shape's
    per-axis ladder rung — exact shape under the default ladder), dtype,
    step count AND scalar coefficients — the scalars are compile-time
    constants the kernel inlines, so two requests with different physics
    constants can never ride the same launch (per-cell coefficient *arrays*
    batch freely, and smaller same-class grids ride under frozen-halo
    masking).
    """
    from repro.core import ir, padding

    lad = padding.parse_ladder(ladder)
    _, scalars = ir.split_coeffs(spec, coeffs)
    cur = state[0]
    return (spec.fingerprint, lad.padded_shape(cur.shape), str(cur.dtype),
            n_steps, tuple(float(x) for x in scalars))


@functools.lru_cache(maxsize=512)
def _padded_launcher(spec, shapes, scalars, padded_shape, n_steps, plan):
    """Jitted pad -> batched-launch -> crop pipeline for one batch signature.

    The whole ragged batch — frozen-halo embedding of every member grid at
    the padding-class shape, the fused `ops.mwd_batched` launch, and the
    per-member crops back to the original shapes — compiles into ONE XLA
    program, so the host pays a single dispatch per batch (eager per-member
    padding would cost dozens of small dispatches and erase the batching
    win on serving-sized grids).  Cached per (op, member shapes, scalars,
    class shape, steps, plan); scalar coefficients stay static so the
    kernels inline them exactly as the unpadded path does.
    """
    from repro.core import ir, padding

    def fn(states, arrays_list):
        run_states, run_coeffs = [], []
        mop = padding.masked_variant(spec)
        for state, arrs in zip(states, arrays_list):
            coeffs = ir.join_coeffs(spec, arrs, scalars)
            mop, st_p, cf_p = padding.pad_problem(spec, state, coeffs,
                                                  padded_shape)
            run_states.append(st_p)
            run_coeffs.append(cf_p)
        from repro.kernels import ops
        cur, prev = ops.mwd_batched(mop, run_states, run_coeffs, n_steps,
                                    plan=plan)
        return tuple(padding.crop_state((cur[i], prev[i]), sh)
                     for i, sh in enumerate(shapes))

    return jax.jit(fn)


def _launch_batch(spec, states, coeffs_list, n_steps, plan, padded_shape):
    """One fused batched MWD launch at the padding-class shape.

    Exact-fit batches (every grid already at `padded_shape`) run `spec`
    directly — the PR-4 path, sharing kernels and plan-registry entries with
    unbatched serving; ragged batches run the fully-jitted
    pad -> launch -> crop pipeline (`_padded_launcher`, frozen-halo masking
    via `repro.core.padding`), which is bitwise-equal per request to its
    sequential run under the same plan (tile plans fix the reduction shape,
    so the comparison is plan-matched — the launched plan is returned so
    callers can replay the reference).  All members must share their scalar
    coefficients (the bucket key guarantees it in the serving loop).
    Returns ``(per-request (cur, prev) list, plan, plan_source)``.
    """
    from repro.core import ir, padding, registry
    from repro.kernels import ops

    shapes = [tuple(s[0].shape) for s in states]
    exact = all(sh == tuple(padded_shape) for sh in shapes)
    spec_used = spec if exact else padding.masked_variant(spec)
    if plan == "auto":
        word = states[0][0].dtype.itemsize
        plan, source = registry.resolve_plan(
            spec_used, tuple(padded_shape), word_bytes=word,
            batch=len(states))
    else:
        source = "explicit"
    if exact:
        cur, prev = ops.mwd_batched(spec, list(states), list(coeffs_list),
                                    n_steps, plan=plan)
        jax.block_until_ready((cur, prev))
        outs = [(cur[i], prev[i]) for i in range(len(states))]
        return outs, plan, source

    split = [ir.split_coeffs(spec, c) for c in coeffs_list]
    scalars = tuple(float(x) for x in split[0][1])
    if any(tuple(float(x) for x in s[1]) != scalars for s in split[1:]):
        raise ValueError(f"{spec.name}: a ragged batch must share scalar "
                         "coefficients (the kernels inline them)")
    launcher = _padded_launcher(spec, tuple(shapes), scalars,
                                tuple(padded_shape), n_steps, plan)
    outs = launcher(tuple(tuple(s) for s in states),
                    tuple(s[0] for s in split))
    jax.block_until_ready(outs)
    return list(outs), plan, source


def serve_queue(requests, *, max_batch: int = 4, batch_window_ms: float = 5.0,
                plan="auto", ladder=None, admission=None, telemetry=None):
    """Continuous-batching serving loop over `requests`.

    Arrivals are admitted into a two-lane bounded queue
    (`repro.core.scheduler.LaneQueue`, per-request `priority`); offers past
    the admission watermark are REJECTED — ``results[rid]`` becomes a
    `Rejected` carrying the retry-after hint.  When a request reaches the
    head of the queue (interactive lane first) the server collects every
    admitted same-class request, then keeps waiting — up to
    `batch_window_ms` past the head's service start, closed EARLY when the
    head's deadline minus the model-predicted launch time says so — while
    the batch is short of `max_batch`; the batch then advances in ONE fused
    `ops.mwd_batched` launch at the padding-class shape (`ladder`; default
    exact shapes = the PR-4 behavior).  Classes are never mixed in a batch.

    `plan` is an `MWDPlan` applied to every launch or "auto", which resolves
    registry-first per (class, batch size) under the ``b<B>`` key.
    `telemetry` is a `repro.launch.telemetry` sink or CLI spec.

    Returns ``(results, records)``: ``results[rid]`` is the request's
    ``(cur, prev)`` (or `Rejected`) and one record dict per launched batch —
    the PR-4 ``{"rids", "size", "key", "done_s"}`` plus ``launch_s``,
    ``lane``, ``padded_shape``, ``waste``, ``plan`` (the concrete `MWDPlan`
    launched — replay ``ops.mwd(..., plan=rec["plan"])`` for a plan-matched
    bitwise reference) and ``plan_source``.
    """
    from repro.core import padding, scheduler

    lad = padding.parse_ladder(ladder)
    tele = tlm.make_telemetry(telemetry)
    own_tele = not isinstance(telemetry, tlm.Telemetry)
    queue = scheduler.LaneQueue(admission or scheduler.AdmissionPolicy())
    est = scheduler.ServiceEstimator()
    agg = tlm.Aggregator()
    pending = sorted(requests, key=lambda r: r.arrival_s)
    keys = {id(r): bucket_key(r.spec, r.state, r.coeffs, r.n_steps,
                              ladder=lad)
            for r in pending}           # immutable per request: compute once
    results: dict[int, object] = {}
    records: list[dict] = []
    t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - t0

    def admit_upto(t: float) -> None:
        while pending and pending[0].arrival_s <= t:
            r = pending.pop(0)
            retry = queue.offer(r, r.priority)
            if retry is None:
                tele.emit("admit", rid=r.rid, lane=r.priority,
                          queue_depth=queue.depth())
            else:
                results[r.rid] = Rejected(retry_after_s=retry)
                agg.on_reject()
                tele.emit("reject", rid=r.rid, lane=r.priority,
                          retry_after_s=retry, queue_depth=queue.depth())

    while pending or len(queue):
        if not len(queue):
            time.sleep(max(0.0, pending[0].arrival_s - now()))
        admit_upto(now())
        if queue.head() is None:
            continue
        head, lane = queue.head()
        key = keys[id(head)]
        close = scheduler.window_close_s(
            now(), batch_window_ms / 1e3, deadline_s=head.deadline_s,
            predicted_launch_s=est.predict(key, max_batch))
        while True:
            admit_upto(now())
            mates = [r for r in queue.items() if keys[id(r)] == key]
            if len(mates) >= max_batch:
                mates = mates[:max_batch]
                break
            upcoming = [r for r in pending
                        if keys[id(r)] == key and r.arrival_s <= close]
            if not upcoming:
                break
            time.sleep(max(0.0, upcoming[0].arrival_s - now()))
        batch = mates
        queue.remove(batch)

        t_launch = time.perf_counter()
        outs, plan_used, source = _launch_batch(
            head.spec, [r.state for r in batch], [r.coeffs for r in batch],
            head.n_steps, plan, key[1])
        launch_s = time.perf_counter() - t_launch
        done = now()
        est.observe(key, len(batch), launch_s)
        shapes = [tuple(r.state[0].shape) for r in batch]
        waste = padding.padding_waste(shapes, key[1])
        agg.on_launch(key, len(batch), launch_s,
                      padded_cells=len(batch) * math.prod(key[1]),
                      real_cells=sum(math.prod(s) for s in shapes),
                      plan_source=source)
        for r, out in zip(batch, outs):
            results[r.rid] = out
            agg.on_done(done - r.arrival_s,
                        deadline_missed=done > r.deadline_s)
        records.append({"rids": [r.rid for r in batch], "size": len(batch),
                        "key": key, "done_s": done, "launch_s": launch_s,
                        "lane": lane, "padded_shape": key[1], "waste": waste,
                        "plan": plan_used, "plan_source": source})
        roll = agg.latency.summary()
        tele.emit("launch", key=str(key), size=len(batch), lane=lane,
                  launch_s=launch_s, waste=waste, plan_source=source,
                  queue_depth=queue.depth(), done_s=done,
                  p50_ms=roll["p50"] * 1e3, p99_ms=roll["p99"] * 1e3)
    tele.emit("summary", **agg.snapshot())
    if own_tele:
        tele.close()
    return results, records


def serve_stencil(name: str, grid, n_steps: int, n_requests: int, *,
                  max_batch: int = 4, batch_window_ms: float = 5.0,
                  arrival_ms: float = 1.0, seed: int = 0, pad=None,
                  telemetry=None, interactive_every: int = 0,
                  deadline_ms: float | None = None,
                  max_queue_depth: int | None = None, plan="auto",
                  dtype=None):
    """Stencil-advance request-queue server: continuous batching over MWD.

    `name` is any operator `repro.core.ir.resolve_op` knows: one of the four
    paper stencils, a registered user-defined `StencilOp`, or a
    ``module.path:ATTR`` import reference.  `grid` is one Z,Y,X shape or a
    list of shapes — requests cycle through them, and the `pad` ladder
    (None/"exact", "pow2", or rungs) groups them into padding classes so
    mixed sizes still share fused launches.  `n_requests` requests (each its
    own grid + coefficients, arriving `arrival_ms` apart) are served through
    `serve_queue`: bucketed by padding class, batched up to `max_batch`
    within `batch_window_ms`, one fused batched MWD launch per batch.  Every
    `interactive_every`-th request (0 = none) rides the interactive lane
    with a `deadline_ms` SLO; `max_queue_depth` bounds admission.  `plan`
    is "auto" — resolve registry-first under the batched ``b<B>`` key (zero
    search/measurement after one `python -m repro.launch.tune`; on a miss
    the model-scored auto-tuner picks it analytically) — or an explicit
    `MWDPlan` applied to every launch, which pins the reduction shape so
    responses can be compared bitwise against same-plan sequential runs.

    `dtype` generates every request at that stream dtype (f32/bf16/fp16):
    the bucket key already separates dtypes, so a reduced-precision tenant
    never shares a fused launch with an f32 one, and plan resolution keys
    on the reduced word size.

    Returns a report dict (plan, source, latency percentiles, GLUP/s,
    per-batch records, padding/rejection/deadline telemetry).
    """
    from repro.core import ir, padding, precision, registry, scheduler
    from repro.core import stencils as stc

    spec = ir.resolve_op(name)
    grids = ([tuple(g) for g in grid] if grid and isinstance(grid[0], (tuple, list))
             else [tuple(grid)] if grid else [registry.default_grid(spec)])
    ladder = padding.parse_ladder(pad)
    dt = precision.parse_dtype(dtype) if dtype is not None else None
    problems = [stc.make_problem(spec, grids[i % len(grids)], dtype=dt,
                                 seed=seed + i)
                for i in range(n_requests)]
    word = problems[0][0][0].dtype.itemsize
    classes: dict[tuple, list] = {}
    for p in problems:
        classes.setdefault(ladder.padded_shape(p[0][0].shape), []).append(p)
    if plan == "auto":
        head_plan, source = registry.resolve_plan(spec, next(iter(classes)),
                                                  word_bytes=word,
                                                  batch=max(1, max_batch))
    else:
        head_plan, source = plan, "explicit"
    print(f"serving {spec.name} on {len(classes)} padding class(es) "
          f"{sorted(classes)}: plan=dw{head_plan.d_w}.nf{head_plan.n_f}."
          f"{'fused' if head_plan.fused else 'row'} ({source}); "
          f"max_batch={max_batch} window={batch_window_ms}ms pad={ladder.mode}")

    # warm EVERY (class, batch size, exact-vs-masked) combination the queue
    # can legally form (window jitter means any size in 1..max_batch can
    # occur): compiling inside the serving loop would corrupt the latency
    # percentiles the server exists to report.  One exact-fit member warms
    # the plain path; one padded member warms the masked path (any masked
    # batch of that size then hits the same compiled kernel).
    for cls, members in classes.items():
        exact = [p for p in members if tuple(p[0][0].shape) == cls]
        ragged = [p for p in members if tuple(p[0][0].shape) != cls]
        for rep in (exact[:1], ragged[:1]):
            for b in (range(1, min(max_batch, len(members)) + 1) if rep
                      else ()):
                _launch_batch(spec, [rep[0][0]] * b, [rep[0][1]] * b,
                              n_steps, plan, cls)

    requests = [
        StencilRequest(
            rid=i, spec=spec, state=problems[i][0], coeffs=problems[i][1],
            n_steps=n_steps, arrival_s=i * arrival_ms / 1e3,
            priority=("interactive" if interactive_every
                      and i % interactive_every == 0 else "batch"),
            deadline_s=(i * arrival_ms / 1e3 + deadline_ms / 1e3
                        if deadline_ms is not None and interactive_every
                        and i % interactive_every == 0 else math.inf))
        for i in range(n_requests)]
    admission = (scheduler.AdmissionPolicy(max_depth=max_queue_depth)
                 if max_queue_depth else None)
    t_start = time.perf_counter()
    results, records = serve_queue(requests, max_batch=max_batch,
                                   batch_window_ms=batch_window_ms,
                                   plan=plan, ladder=ladder,
                                   admission=admission, telemetry=telemetry)
    t_wall = time.perf_counter() - t_start

    done_by_rid = {rid: rec["done_s"] for rec in records
                   for rid in rec["rids"]}
    served = [r for r in requests if r.rid in done_by_rid]
    rejected = [r for r in requests if isinstance(results.get(r.rid), Rejected)]
    misses = sum(done_by_rid[r.rid] > r.deadline_s for r in served)
    lat = sorted(done_by_rid[r.rid] - r.arrival_s for r in served)
    p50, p95, p99 = (np.percentile(lat, [50, 95, 99]) if lat
                     else (0.0, 0.0, 0.0))
    lups = sum(float(np.prod(r.state[0].shape)) * n_steps for r in served)
    glups = lups / t_wall / 1e9
    sizes = [rec["size"] for rec in records]
    waste = (sum(rec["waste"] * rec["size"] for rec in records)
             / max(sum(sizes), 1))
    print(f"served {len(served)}/{n_requests} requests x {n_steps} steps in "
          f"{len(records)} batches (sizes {sizes}): "
          f"p50 {p50*1e3:.1f}ms p95 {p95*1e3:.1f}ms p99 {p99*1e3:.1f}ms, "
          f"agg {glups:.4f} GLUP/s; rejected={len(rejected)} "
          f"deadline_misses={misses} waste={waste:.3f}")
    return {"plan": head_plan, "source": source, "results": results,
            "records": records, "latencies_s": lat, "p50_ms": p50 * 1e3,
            "p95_ms": p95 * 1e3, "p99_ms": p99 * 1e3, "glups": glups,
            "batch_sizes": sizes, "served": len(served),
            "rejected": len(rejected), "deadline_misses": misses,
            "padding_waste": waste,
            "classes": {str(c): len(m) for c, m in classes.items()}}


def build_parser() -> argparse.ArgumentParser:
    """CLI of the serving launcher (split out so tests can parse args)."""
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", default="llama3.2-1b",
                    choices=list(configs.ARCH_IDS))
    ap.add_argument("--stencil", default=None,
                    help="serve stencil advances instead of an LM: a paper "
                         "op, a registered custom op, or module.path:ATTR")
    ap.add_argument("--op-module", default=None,
                    help="import this module first (it registers custom "
                         "StencilOps via repro.core.ir.register)")
    ap.add_argument("--grid", type=str, default=None,
                    help="Z,Y,X stencil grid, or several separated by ';' "
                         "for mixed-size traffic (default: sanity scale)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4,
                    help="time steps advanced per stencil request")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="max requests fused into one batched MWD launch")
    ap.add_argument("--batch-window-ms", type=float, default=5.0,
                    help="max wait for same-class arrivals before launching")
    ap.add_argument("--arrival-ms", type=float, default=1.0,
                    help="synthetic inter-arrival gap between requests")
    ap.add_argument("--pad", default="exact",
                    help="padding ladder: 'exact', 'pow2', or rungs '8,16,32'"
                         " — mixed sizes in one class share fused launches")
    ap.add_argument("--dtype", default=None,
                    help="stream dtype of every stencil request (f32/bf16/"
                         "fp16); bucket keys separate dtypes, so reduced-"
                         "precision and f32 tenants never share a launch")
    ap.add_argument("--telemetry", default=None,
                    help="live telemetry sink: 'stdout' or 'jsonl:<path>'")
    ap.add_argument("--interactive-every", type=int, default=0,
                    help="every Nth request rides the interactive lane "
                         "(0 = all batch lane)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="SLO deadline for interactive-lane requests")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission bound per lane; overflow is rejected "
                         "with a retry-after hint")
    # BooleanOptionalAction so --no-reduced can actually reach the
    # full-size config ('store_true' with default=True made it unreachable)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--spec", default=None,
                    help="device spec name or spec-file path plan "
                         "resolution prices against (default: "
                         "$REPRO_DEVICE_SPEC or tpu-v5e)")
    return ap


def main(argv=None):
    """CLI entry point: stencil request-queue server or LM decode loop."""
    compile_cache.enable()
    args = build_parser().parse_args(argv)

    if args.spec:
        from repro.core import specs as devspecs
        devspecs.set_default_spec(args.spec)
    if args.op_module:
        import importlib
        importlib.import_module(args.op_module)
    if args.stencil:
        grid = ([tuple(int(x) for x in g.split(","))
                 for g in args.grid.split(";")] if args.grid else None)
        if grid and len(grid) == 1:
            grid = grid[0]
        serve_stencil(args.stencil, grid, args.steps, args.requests,
                      max_batch=args.max_batch,
                      batch_window_ms=args.batch_window_ms,
                      arrival_ms=args.arrival_ms, pad=args.pad,
                      telemetry=args.telemetry,
                      interactive_every=args.interactive_every,
                      deadline_ms=args.deadline_ms,
                      max_queue_depth=args.max_queue_depth,
                      dtype=args.dtype)
        return

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    if not cfg.supports_decode:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode")
    mesh = elastic.build_mesh()
    params = jax.device_put(tree_init(lm.param_specs(cfg), seed=0),
                            shd.param_shardings(mesh, lm.param_specs(cfg)))

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        _, cache = prefill_into_cache(cfg, params, prompts, args.gen)
        t_prefill = time.perf_counter() - t0

        serve = jax.jit(tsteps.make_serve_step(cfg))
        toks = prompts[:, -1:]
        out = []
        t0 = time.perf_counter()
        for _ in range(args.gen):
            toks, _, cache = serve(params, cache, toks)
            out.append(toks)
        jax.block_until_ready(toks)
        t_gen = time.perf_counter() - t0

    gen = jnp.concatenate(out, axis=1)
    tput = args.batch * args.gen / t_gen
    print(f"prefill {args.batch}x{args.prompt_len} in {t_prefill*1e3:.0f}ms; "
          f"generated {args.gen} tokens/seq at {tput:.1f} tok/s "
          f"(batch={args.batch})")
    print("sample token ids:", np.asarray(gen[0])[:16].tolist())


if __name__ == "__main__":
    main()
