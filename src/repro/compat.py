"""Portable plan translation across device specs."""

from __future__ import annotations


def translate_entry(entry, op, grid_shape, *, to_spec, word_bytes=4, batch=1):
    """Translate a registry entry tuned under another spec to `to_spec`.

    A plan tuned (measured) under device spec A is still a *valid schedule*
    on device B as long as B's kernel constraints accept it; what does NOT
    carry over is the score. Translation policy:

      1. refuse (return None) when the plan is kernel-invalid for the op,
         when its VMEM footprint does not fit under `to_spec` (Eq. 3), or
         when either analytic model score is non-finite/non-positive — a
         plan we cannot price honestly is not resolved at all, and the
         caller falls back to the analytic tuner;
      2. otherwise rescale: score_B = score_A * model_B(plan)/model_A(plan),
         the measured score corrected by the ratio of analytic predictions
         under the two machine models. No re-measurement happens.

    The returned entry carries ``source="translated:<spec A>"``, the target
    spec's name/fingerprint, and the rescaled score. Imports are deferred
    so importing repro.compat stays jax-light.
    """
    import dataclasses
    import math

    from repro.core import autotune, models, specs as devspecs

    if not entry.spec or entry.spec == to_spec.name:
        return None                       # nothing to translate
    try:
        from_spec = devspecs.get_spec(entry.spec)
    except devspecs.SpecError:
        return None                       # unknown source spec: refuse
    plan = entry.plan
    if not autotune._plan_valid(op, plan):
        return None
    nx = grid_shape[2]
    if not models.vmem_fits(op, plan.d_w, plan.n_f, nx // plan.tg_x, to_spec,
                            word_bytes):
        return None
    score_a = autotune.model_score(op, grid_shape, word_bytes, from_spec,
                                   batch)(plan)
    score_b = autotune.model_score(op, grid_shape, word_bytes, to_spec,
                                   batch)(plan)
    if not (math.isfinite(score_a) and math.isfinite(score_b)
            and score_a > 0.0 and score_b > 0.0):
        return None
    return dataclasses.replace(
        entry,
        score=entry.score * (score_b / score_a),
        source=f"translated:{entry.spec}",
        fingerprint=devspecs.fingerprint(to_spec),
        spec=to_spec.name,
    )
