"""The comparison that decides ``correct``: the numbers compared, and the
limits they are held to.

Training cells compare what the timed object (the ``Trainer`` the window then
drives) produced in its first steps with the plain float32 reference following
the same steps on the same rows:

``loss_gap``         largest |loss - reference loss| over the steps followed;
``grad_norm_gap``    worst leaf of | ||g|| - ||g_ref|| | / max(||g_ref||,
                     median leaf ||g_ref||), g the first gradient as the
                     optimizer got it (read back from its state after step 1);
``grad_rel_diff``    ||g - g_ref|| / ||g_ref|| over all leaves together: an
                     aggregate over every element of the first gradient, the
                     number that separates precisions (a norm gap averages
                     rounding away; this does not);
``extra_rel_diff``   (models with non-trainable state) ||d - d_ref|| / ||d_ref||
                     over all leaves, d the change of that state (BatchNorm's
                     running statistics) in the first step: the forward pass's
                     per-channel means and variances, each an average over a
                     whole batch, so weight rounding shows and noise does not;
``delta_norm_gap``   worst leaf of the same gap for the norm of the parameters'
                     change over the steps (there to catch a step that returns
                     its state unchanged: gap 1.0).

The serving cell compares a seeded sample of the replies with the reference's
inference logits: ``logit_rel_rms`` = rms(reply - ref) / rms(ref) over the
sample.

Limits live in ``benchmark/correctness/<cell>.json`` beside the per-seed
readings they were set from.
"""

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def norm_gap(got, ref):
    """Worst leaf of |got - ref| / max(ref, median leaf ref) for two dicts of
    leaf norms."""
    floor = float(np.median(list(ref.values())))
    return max(abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref)


def training_numbers(program, reference):
    """``program``/``reference``: {"losses", "first_gradient" (name -> array),
    "delta_norms" (name -> float)} with the same leaf names."""
    gp, gr = program["first_gradient"], reference["first_gradient"]
    if set(gp) != set(gr):
        raise ValueError("leaf names differ: {}".format(
            sorted(set(gp) ^ set(gr))[:6]))
    diff = ref_sq = 0.0
    norms_p, norms_r = {}, {}
    for k, r in gr.items():
        r = np.asarray(r, np.float32).ravel()
        p = np.asarray(gp[k], np.float32).ravel()
        if p.shape != r.shape:
            raise ValueError("leaf {}: {} against {}".format(
                k, p.shape, r.shape))
        d = p - r
        diff += float(np.dot(d, d))
        rr = float(np.dot(r, r))
        ref_sq += rr
        norms_p[k], norms_r[k] = float(np.sqrt(np.dot(p, p))), np.sqrt(rr)
    extra = {}
    if reference.get("extra_delta"):
        ep, er = program["extra_delta"], reference["extra_delta"]
        if set(ep) != set(er):
            raise ValueError("state leaf names differ: {}".format(
                sorted(set(ep) ^ set(er))[:6]))
        num = sum(float(np.sum((np.asarray(ep[k], np.float64)
                                - er[k]) ** 2)) for k in er)
        den = sum(float(np.sum(np.asarray(er[k], np.float64) ** 2))
                  for k in er)
        extra["extra_rel_diff"] = float(np.sqrt(num / den))
    return dict(extra, **{
        "loss_gap": float(max(abs(a - b) for a, b in zip(
            program["losses"], reference["losses"]))),
        "grad_norm_gap": float(norm_gap(norms_p, norms_r)),
        "grad_rel_diff": float(np.sqrt(diff / ref_sq)),
        "delta_norm_gap": float(norm_gap(program["delta_norms"],
                                         reference["delta_norms"])),
    })


def serving_numbers(replies, reference):
    """Lists of [n_i, classes] arrays, request by request."""
    got = np.concatenate([np.asarray(r, np.float32) for r in replies])
    ref = np.concatenate([np.asarray(r, np.float32) for r in reference])
    return {"logit_rel_rms": float(
        np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)))}


def load_limits(cell):
    with open(os.path.join(HERE, "correctness", cell + ".json")) as f:
        return json.load(f)["limits"]


def judge(numbers, limits):
    """[(name, value, limit, ok)] for every number a limit names; a number
    that is missing or not finite fails."""
    rows = []
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        ok = value is not None and np.isfinite(value) and value <= limit
        rows.append((name, value, limit, bool(ok)))
    return rows


def verdict(report, limits, out=print):
    """(correct, rows): every number beside its limit, printed; ``correct`` is
    true only when limits exist, every judged number holds, and the run
    recorded no problem of its own (conservation, finiteness, row counts)."""
    rows = judge(report.get("numbers", {}), limits)
    for name, value, limit, ok in rows:
        out("perfbench: compared {} = {} against limit {}: {}".format(
            name, value, limit, "ok" if ok else "NOT ok"))
    for name, value in sorted(report.get("numbers", {}).items()):
        if name not in limits:
            out("perfbench: read {} = {} (no limit: not judged)".format(
                name, value))
    for name, value in sorted(report.get("control_numbers", {}).items()):
        out("perfbench: control {} = {}".format(name, value))
    for problem in report.get("problems", []):
        out("perfbench: NOT ok: " + problem)
    if not rows:
        out("perfbench: no limits for this cell under correctness/: nothing "
            "was judged, so correct is false")
    correct = bool(rows) and all(ok for _, _, _, ok in rows) \
        and not report.get("problems")
    return correct, rows
