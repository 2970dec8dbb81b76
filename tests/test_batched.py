"""Batched multi-request MWD serving + the PR's serving/distributed fixes.

Covers the batch axis end-to-end: `ops.mwd_batched` bitwise-equal to the
sequential per-item loop (all four paper ops + a custom IR op), the batched
``b<B>`` registry key schema (separation from B=1, legacy-key upgrade), the
batch-amortized model score, the request-queue server (bucketing, dynamic
batching, percentiles), the distributed auto-plan per-shard resolution
helpers, and the serve-loop cache-sizing / --reduced bugfixes.
"""

import jax
import json
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, ir, registry as reg, stencils as st
from repro.core.mwd import MWDPlan
from repro.kernels import ops

SPEC = st.SPECS["7pt-const"]
GRID = (8, 14, 10)


def _custom_mixed_op() -> ir.StencilOp:
    # NOT among the paper's four: mixed const + array coefficients, so the
    # batched path must stack the per-request stream AND share the scalars
    taps = [ir.Tap(0, 0, 0, ir.const(0)),
            ir.Tap(0, 0, 1, ir.array(0)), ir.Tap(0, 0, -1, ir.array(0))]
    taps += [ir.Tap(*o, ir.const(1)) for o in
             ((0, 1, 0), (0, -1, 0), (1, 0, 0), (-1, 0, 0))]
    return ir.StencilOp("bat-custom7", tuple(taps),
                        default_scalars=(0.3, 0.1), coeff_scale=0.1)


CUSTOM = _custom_mixed_op()


# ---------------------------------------------------------------------------
# Tentpole: one fused launch == the sequential per-item loop, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", list(st.SPECS) + ["bat-custom7"])
def test_mwd_batched_bitwise_equals_per_item_loop(name):
    spec = CUSTOM if name == "bat-custom7" else st.SPECS[name]
    shape = (8, 14, 10) if spec.radius == 1 else (10, 18, 14)
    b = 3 if spec.radius == 1 else 2
    d_w, n_f, t_steps = 4 * spec.radius, 2, 3
    probs = [st.make_problem(spec, shape, seed=i) for i in range(b)]
    states = [p[0] for p in probs]
    coeffs = [p[1] for p in probs]
    want = [ops.mwd(spec, s, c, t_steps, d_w=d_w, n_f=n_f, fused=True)
            for s, c in zip(states, coeffs)]
    got = ops.mwd_batched(spec, states, coeffs, t_steps, d_w=d_w, n_f=n_f)
    assert got[0].shape == (b,) + shape
    for i in range(b):
        np.testing.assert_array_equal(np.asarray(want[i][0]),
                                      np.asarray(got[0][i]))
        np.testing.assert_array_equal(np.asarray(want[i][1]),
                                      np.asarray(got[1][i]))


def test_mwd_batched_per_row_mode_bitwise():
    """fused=False (one launch per diamond row) batches too."""
    probs = [st.make_problem(SPEC, (8, 12, 10), seed=i) for i in range(2)]
    states = [p[0] for p in probs]
    coeffs = [p[1] for p in probs]
    want = [ops.mwd(SPEC, s, c, 3, d_w=4, n_f=2, fused=False)
            for s, c in zip(states, coeffs)]
    got = ops.mwd_batched(SPEC, states, coeffs, 3, d_w=4, n_f=2, fused=False)
    for i in range(2):
        np.testing.assert_array_equal(np.asarray(want[i][0]),
                                      np.asarray(got[0][i]))


def test_mwd_batched_prestacked_states_and_shared_coeffs():
    """The (B, nz, ny, nx) stacked-state form + one shared packed coeff set."""
    probs = [st.make_problem(SPEC, GRID, seed=i) for i in range(3)]
    cur = jnp.stack([p[0][0] for p in probs])
    prev = jnp.stack([p[0][1] for p in probs])
    shared = probs[0][1]
    want = [ops.mwd(SPEC, p[0], shared, 2, d_w=4, n_f=2) for p in probs]
    got = ops.mwd_batched(SPEC, (cur, prev), shared, 2, d_w=4, n_f=2)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(want[i][0]),
                                      np.asarray(got[0][i]))


def test_mwd_batched_scalar_mismatch_raises():
    """Scalars are compile-time constants: a mixed-scalar batch must refuse
    rather than silently run every request with item 0's physics."""
    probs = [st.make_problem(SPEC, GRID, seed=i) for i in range(2)]
    coeffs = [probs[0][1], (0.9, 0.2)]          # different scalar physics
    with pytest.raises(ValueError, match="scalar"):
        ops.mwd_batched(SPEC, [p[0] for p in probs], coeffs, 2, d_w=4, n_f=2)


def test_mwd_batched_wrong_coeff_count_raises():
    probs = [st.make_problem(SPEC, GRID, seed=i) for i in range(3)]
    with pytest.raises(ValueError, match="coefficient"):
        ops.mwd_batched(SPEC, [p[0] for p in probs],
                        [probs[0][1]], 2, d_w=4, n_f=2)


def test_mwd_batched_plan_auto_uses_batched_registry_key(tmp_path,
                                                         monkeypatch):
    """plan="auto" at batch B resolves the b<B> entry with zero search."""
    path = str(tmp_path / "plans.json")
    monkeypatch.setenv(reg.ENV_VAR, path)
    b = 3
    r = reg.PlanRegistry(path)
    r.put(SPEC, GRID, MWDPlan(d_w=4, n_f=2), 5.0, batch=b)
    r.put(SPEC, GRID, MWDPlan(d_w=2, n_f=1), 5.0)       # the B=1 entry
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **k: pytest.fail("searched on a hit"))
    probs = [st.make_problem(SPEC, GRID, seed=i) for i in range(b)]
    states = [p[0] for p in probs]
    coeffs = [p[1] for p in probs]
    got = ops.mwd_batched(SPEC, states, coeffs, 3, plan="auto")
    want = ops.mwd_batched(SPEC, states, coeffs, 3, d_w=4, n_f=2)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))


# ---------------------------------------------------------------------------
# Registry: the b<B> key schema
# ---------------------------------------------------------------------------

def test_plan_key_batch_segment():
    k1 = reg.plan_key(SPEC, GRID)
    k4 = reg.plan_key(SPEC, GRID, batch=4)
    assert k1.endswith("|b1") and k4.endswith("|b4")
    assert k1 != k4
    with pytest.raises(ValueError, match="batch"):
        reg.plan_key(SPEC, GRID, batch=0)


def test_batched_entries_do_not_collide_with_b1(tmp_path):
    r = reg.PlanRegistry(str(tmp_path / "plans.json"))
    r.put(SPEC, GRID, MWDPlan(d_w=2, n_f=1), 1.0)
    r.put(SPEC, GRID, MWDPlan(d_w=8, n_f=2), 2.0, batch=4)
    assert r.get(SPEC, GRID).plan == MWDPlan(d_w=2, n_f=1)
    assert r.get(SPEC, GRID, batch=4).plan == MWDPlan(d_w=8, n_f=2)
    assert r.get(SPEC, GRID, batch=2) is None


def test_legacy_key_without_batch_segment_upgrades_to_b1(tmp_path):
    """Pre-batch registry files keep working: keys load as B=1 entries and
    the next save rewrites them under the new schema."""
    from repro import hw

    path = tmp_path / "plans.json"
    new_key = reg.plan_key(SPEC, GRID)
    assert new_key.endswith("|b1")
    legacy_key = new_key[:-len("|b1")]
    entry = {"plan": {"d_w": 4, "n_f": 2}, "score": 1.5,
             "source": "measured", "fingerprint": hw.fingerprint()}
    path.write_text(json.dumps({"version": reg.SCHEMA_VERSION,
                                "plans": {legacy_key: entry}}))
    r = reg.PlanRegistry(str(path))
    got = r.get(SPEC, GRID)
    assert got is not None and got.plan == MWDPlan(d_w=4, n_f=2)
    assert r.get(SPEC, GRID, batch=4) is None   # never leaks into batched
    r.save()
    assert list(json.load(open(path))["plans"]) == [new_key]


# ---------------------------------------------------------------------------
# Batch-aware model
# ---------------------------------------------------------------------------

def test_model_score_batch_amortizes_dispatch():
    from repro.core import models

    plan = MWDPlan(d_w=4, n_f=2)
    s1 = autotune.model_score(SPEC, GRID)(plan)
    s8 = autotune.model_score(SPEC, GRID, batch=8)(plan)
    # sanity-scale grids are dispatch-dominated: amortization must show
    assert s8 > s1
    assert models.batch_amortized_time(1e-6, 4) == pytest.approx(
        4e-6 + models.T_DISPATCH_S)
    a2, a8 = (models.batch_amortization(1e-7, b) for b in (2, 8))
    assert 1.0 < a2 < a8 < 8.0
    with pytest.raises(ValueError, match="batch"):
        models.batch_amortized_time(1e-6, 0)


def test_measure_score_times_batched_launch():
    """batch>1 measures ONE mwd_batched call advancing B problems."""
    scorer = autotune.measure_score(SPEC, (6, 10, 8), n_steps=2, reps=2,
                                    warmup=1, batch=2)
    s = scorer(MWDPlan(d_w=2, n_f=1))
    assert s > 0 and scorer.measurements == 1
    assert scorer(MWDPlan(d_w=2, n_f=3)) == -math.inf   # pruned, not timed
    assert scorer.measurements == 1


def test_tune_cli_batched_entry(tmp_path, monkeypatch):
    """`tune --batch B` persists under b<B> without touching the B=1 key."""
    from repro.launch import tune

    def fake_measure_score(spec, grid_shape, *a, **k):
        inner = autotune.model_score(spec, grid_shape,
                                     batch=k.get("batch", 1))

        def score(plan):
            s = inner(plan)
            if not math.isinf(s):
                score.measurements += 1
            return s

        score.measurements = 0
        return score

    monkeypatch.setattr(autotune, "measure_score", fake_measure_score)
    path = str(tmp_path / "plans.json")
    out = tune.main(["--stencil", "7pt-const", "--registry", path,
                     "--batch", "4", "--max-evals", "6"])
    assert out[0]["source"] == "measured"
    r = reg.PlanRegistry(path)
    assert r.get(SPEC, reg.default_grid(SPEC), batch=4) is not None
    assert r.get(SPEC, reg.default_grid(SPEC)) is None      # B=1 untouched
    # second batched run: pure cache hit
    again = tune.main(["--stencil", "7pt-const", "--registry", path,
                       "--batch", "4"])
    assert again[0]["source"] == "cached"
    assert again[0]["measurements"] == 0


# ---------------------------------------------------------------------------
# Distributed auto-plan resolution (per-shard shape, capping, rejection)
# ---------------------------------------------------------------------------

def test_local_extended_shape_and_cap():
    from repro.distributed import stepper

    mesh = jax.make_mesh((1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    assert stepper.local_extended_shape(SPEC, mesh, (8, 12, 10),
                                        t_block=2) == (12, 16, 14)
    capped = stepper.cap_plan_d_w(SPEC, MWDPlan(d_w=64, n_f=4), 14)
    assert capped.d_w == 14 and capped.d_w % (2 * SPEC.radius) == 0
    assert capped.d_w % capped.n_f == 0
    keep = MWDPlan(d_w=4, n_f=2)
    assert stepper.cap_plan_d_w(SPEC, keep, 14) is keep
    # radius-4 op: the cap must stay a multiple of 2R
    spec25 = st.SPECS["25pt-const"]
    capped25 = stepper.cap_plan_d_w(spec25, MWDPlan(d_w=32, n_f=2), 20)
    assert capped25.d_w == 16 and capped25.d_w % 8 == 0


def test_run_distributed_rejects_oversized_explicit_plan():
    from repro.core import stencils
    from repro.distributed import stepper

    mesh = jax.make_mesh((1, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    state, coeffs = stencils.make_problem(SPEC, (8, 12, 10), seed=0)
    with pytest.raises(ValueError, match="exceeds the per-shard"):
        stepper.run_distributed(SPEC, mesh, state, coeffs, 4, t_block=2,
                                plan=MWDPlan(d_w=64, n_f=2))


# ---------------------------------------------------------------------------
# Request-queue serving: bucketing, dynamic batching, reporting
# ---------------------------------------------------------------------------

def _requests(serve, spec, shapes_seeds, n_steps, arrival_s=0.0):
    reqs = []
    for i, seed in enumerate(shapes_seeds):
        state, coeffs = st.make_problem(spec, GRID, seed=seed)
        reqs.append(serve.StencilRequest(rid=len(reqs), spec=spec,
                                         state=state, coeffs=coeffs,
                                         n_steps=n_steps,
                                         arrival_s=arrival_s))
    return reqs


def test_bucket_key_separates_ops_and_scalars():
    from repro.launch import serve

    state, coeffs = st.make_problem(SPEC, GRID, seed=0)
    k = serve.bucket_key(SPEC, state, coeffs, 2)
    assert serve.bucket_key(SPEC, state, coeffs, 2) == k
    assert serve.bucket_key(SPEC, state, coeffs, 3) != k          # steps
    assert serve.bucket_key(SPEC, state, (0.9, 0.2), 2) != k      # scalars
    var_state, var_coeffs = st.make_problem(st.SPECS["7pt-var"], GRID, seed=0)
    assert serve.bucket_key(st.SPECS["7pt-var"], var_state,
                            var_coeffs, 2) != k                   # op fp


def test_serve_queue_batches_per_bucket_bitwise():
    """Mixed-op queue: batches never mix buckets; results == per-item MWD."""
    from repro.launch import serve

    plan = MWDPlan(d_w=4, n_f=2)
    var = st.SPECS["7pt-var"]
    reqs = []
    for i, spec in enumerate([SPEC, var, SPEC, SPEC]):
        state, coeffs = st.make_problem(spec, GRID, seed=10 + i)
        reqs.append(serve.StencilRequest(rid=i, spec=spec, state=state,
                                         coeffs=coeffs, n_steps=2))
    results, records = serve.serve_queue(reqs, max_batch=4,
                                         batch_window_ms=1.0, plan=plan)
    assert sorted(r["size"] for r in records) == [1, 3]
    by_rid = {r.rid: r for r in reqs}
    for rec in records:                  # a batch never mixes buckets
        keys = {serve.bucket_key(by_rid[i].spec, by_rid[i].state,
                                 by_rid[i].coeffs, by_rid[i].n_steps)
                for i in rec["rids"]}
        assert keys == {rec["key"]}
    for r in reqs:
        want = ops.mwd(r.spec, r.state, r.coeffs, 2, plan=plan)
        np.testing.assert_array_equal(np.asarray(want[0]),
                                      np.asarray(results[r.rid][0]))


def test_serve_queue_respects_max_batch():
    from repro.launch import serve

    reqs = _requests(serve, SPEC, range(5), n_steps=2)
    _, records = serve.serve_queue(reqs, max_batch=2, batch_window_ms=1.0,
                                   plan=MWDPlan(d_w=4, n_f=2))
    assert [r["size"] for r in records] == [2, 2, 1]
    assert sorted(rid for r in records for rid in r["rids"]) == list(range(5))


def test_serve_stencil_reports_percentiles(tmp_path, monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setenv(reg.ENV_VAR, str(tmp_path / "plans.json"))
    report = serve.serve_stencil(
        "7pt-const", (6, 10, 8), n_steps=2, n_requests=4, max_batch=2,
        batch_window_ms=2.0, arrival_ms=0.1)
    out = capsys.readouterr().out
    assert "p50" in out and "p95" in out and "p99" in out
    assert "GLUP/s" in out
    assert report["p50_ms"] <= report["p95_ms"] <= report["p99_ms"]
    assert report["glups"] > 0
    assert sum(report["batch_sizes"]) == 4
    assert len(report["results"]) == 4


# ---------------------------------------------------------------------------
# Serve-loop bugfixes: cache sizing + --reduced flag
# ---------------------------------------------------------------------------

def test_prefill_cache_sized_for_prompt_plus_gen(monkeypatch):
    """The KV/state cache must hold prompt + gen tokens (it used to be a
    fixed prompt+64, silently overflowing for --gen > 64)."""
    from repro import configs
    from repro.launch import serve
    from repro.models import lm
    from repro.models.params import tree_init

    cfg = configs.reduced(configs.get("llama3.2-1b"), n_layers=1, d_model=64)
    params = tree_init(lm.param_specs(cfg), seed=0)
    seen = {}
    real = lm.init_cache

    def spy(cfg_, b, seq_len, **kw):
        seen["seq_len"] = seq_len
        return real(cfg_, b, seq_len, **kw)

    monkeypatch.setattr(serve.lm, "init_cache", spy)
    toks = jnp.zeros((1, 3), jnp.int32)
    serve.prefill_into_cache(cfg, params, toks, gen=70)
    assert seen["seq_len"] >= 3 + 70
    with pytest.raises(ValueError, match="gen"):
        serve.prefill_into_cache(cfg, params, toks, gen=-1)
    with pytest.raises(ValueError, match="cannot hold"):    # undersized
        serve.prefill_into_cache(cfg, params, toks, gen=70, cache_len=60)


def test_reduced_flag_boolean_optional():
    """--no-reduced must reach the full-size config (it used to be
    store_true with default=True: always True)."""
    from repro.launch import serve

    ap = serve.build_parser()
    assert ap.parse_args([]).reduced is True
    assert ap.parse_args(["--reduced"]).reduced is True
    assert ap.parse_args(["--no-reduced"]).reduced is False
