"""The comparisons that decide `correct`, as plain functions over host
arrays, so that the runs, the limit readings and the self-checks all use
the same arithmetic."""
import numpy as np


def leaf_norms(tree):
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def worst_norm_gap(got, want, skip=()):
    """Largest over leaves of |‖got‖ - ‖want‖| / max(‖want‖, median
    leaf's ‖want‖): the gap between the norms, not the norm of the
    difference, against the larger of that leaf's and the median leaf's
    reference norm (some gradients are all but zero). Returns (gap,
    leaf)."""
    worst, where = 0.0, None
    for k, gap in norm_gaps(got, want, skip).items():
        if gap > worst:
            worst, where = gap, k
    return worst, where


def norm_gaps(got, want, skip=()):
    """{leaf: |‖got‖ - ‖want‖| / max(‖want‖, median ‖want‖)}."""
    gn, wn = leaf_norms(got), leaf_norms(want)
    names = [k for k in wn if k not in skip]
    med = float(np.median([wn[k] for k in names])) if names else 0.0
    out = {}
    for k in names:
        denom = max(wn[k], med)
        if denom > 0.0:
            out[k] = abs(gn[k] - wn[k]) / denom
    return out


def rel_diffs(got, want, skip=()):
    """{leaf: ‖got - want‖ / max(‖want‖, median ‖want‖)}: the norm of
    the difference. Rounding in a lower precision moves this in
    proportion, while the gap between norms sees it only squared."""
    wn = leaf_norms(want)
    names = [k for k in wn if k not in skip]
    med = float(np.median([wn[k] for k in names])) if names else 0.0
    out = {}
    for k in names:
        denom = max(wn[k], med)
        if denom > 0.0:
            d = np.asarray(got[k], np.float64) - np.asarray(want[k],
                                                            np.float64)
            out[k] = float(np.linalg.norm(d.ravel())) / denom
    return out


def still_leaves(ref_grad, share=1e-3):
    """Leaves whose reference gradient is nought to rounding: under a
    thousandth of the median leaf's norm. They move by the decay and
    round-off alone and are left out of the change."""
    n = leaf_norms(ref_grad)
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v < share * med}


def first_gradient(w0, w1, hyper, decays):
    """The gradient the optimizer got at step 1, worked out from the
    state after it: SGD with momentum from zero state moves a weight by
    -lr * (g + wd * w0)."""
    lr, wd = hyper["learning_rate"], hyper["wd"]
    return {k: -(np.asarray(w1[k], np.float64)
                 - np.asarray(w0[k], np.float64)) / lr
            - (wd if decays(k) else 0.0) * np.asarray(w0[k], np.float64)
            for k in w0}


def change(w0, w3):
    return {k: np.asarray(w3[k], np.float64) - np.asarray(w0[k], np.float64)
            for k in w0}


def training_numbers(prog, ref, w0, hyper, decays):
    """The numbers compared in a training cell: {name: value}.
    `prog` = {"losses": [l1, l2, l3], "w1": params after step 1 (or
    "grad1": the first gradient itself, where a reference stands in the
    program's place), "w3": after step 3}; `ref` = what
    reference.train_steps returned."""
    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"],
                                              ref["losses"]))
    g_prog = prog["grad1"] if "grad1" in prog else first_gradient(
        w0, prog["w1"], hyper, decays)
    g_prog = {k: np.asarray(v, np.float64) for k, v in g_prog.items()}
    g_ref = {k: np.asarray(v, np.float64) for k, v in ref["grad1"].items()}
    grad_gap, grad_leaf = worst_norm_gap(g_prog, g_ref)
    skip = still_leaves(g_ref)
    d_gap, d_leaf = worst_norm_gap(change(w0, prog["w3"]),
                                   change(w0, ref["params"]), skip=skip)
    numbers_diff = {}
    if prog.get("probs1") is not None and ref.get("probs1") is not None:
        a = np.asarray(prog["probs1"], np.float64)
        b = np.asarray(ref["probs1"], np.float64)
        if a.shape == b.shape:
            numbers_diff["probs1_rel_diff"] = float(
                np.linalg.norm(a - b) / np.linalg.norm(b))
    numbers_diff["grad1_median_rel_diff"] = float(np.median(list(
        rel_diffs(g_prog, g_ref).values())))
    numbers_diff["change3_median_rel_diff"] = float(np.median(list(
        rel_diffs(change(w0, prog["w3"]), change(w0, ref["params"]),
                  skip).values())))
    g_all = norm_gaps(g_prog, g_ref)
    d_all = norm_gaps(change(w0, prog["w3"]), change(w0, ref["params"]),
                      skip)
    return {"loss_gap": loss_gap, "loss1_gap": abs(
                prog["losses"][0] - ref["losses"][0]),
            "grad1_norm_gap": grad_gap, "change3_norm_gap": d_gap,
            "grad1_median_gap": float(np.median(list(g_all.values()))),
            "change3_median_gap": float(np.median(list(d_all.values()))),
            **numbers_diff}, {
        "grad1_leaf": grad_leaf, "change3_leaf": d_leaf,
        "still_leaves": sorted(skip),
        "grad1_top": sorted(g_all.items(), key=lambda kv: -kv[1])[:4],
        "change3_top": sorted(d_all.items(), key=lambda kv: -kv[1])[:4]}


def judge(numbers, limits):
    """{name: [value, limit]} and whether every number is within its
    limit (a number that is not finite fails)."""
    out, ok = {}, True
    for k, lim in limits.items():
        v = numbers[k]
        out[k] = [float(v), float(lim)]
        if not (v == v) or v > lim:
            ok = False
    return out, ok
