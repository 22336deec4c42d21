"""The numbers that decide ``correct``: the program's first segment of
rounds against the plain reference over the same rounds.

* ``loss_gap``: the largest relative gap of a round's logged loss;
* ``grad_norm_gap``: the largest relative gap of a round's mean client
  gradient norm at the aggregating step (the program's in-trace telemetry);
* ``dx_gap`` and ``d_gap``: over model leaves, the largest gap between the
  program's and the reference's norm of the parameters' change (all clients
  together) and of the drift d, each over the larger of the reference's
  norm of that leaf and of the median leaf;
* ``sketch_gap``: over rounds and the two sketched sources (each client's
  norm of d, ``d_norm``, and of x less the clients' mean, ``drift``), the
  largest relative gap of a value the program's sketch reports: each top-k
  value against the reference's norm of the client its id names, and the
  p50, p90, p99 and max against the same quantiles of the reference's
  norms. Top-k ids that repeat or name no client fail;
* ``hist_moved``: over rounds and sources, how many clients the program's
  log10 histogram holds in a bin that neither the reference's norm of that
  client nor the program's own reported norm of it falls in (so a norm on
  a bin edge may go either way). The comparison is exact: limit 0.

Leaves whose warm-up gradient in the reference is under a thousandth of the
median leaf's are left out of ``dx_gap`` and ``d_gap``: they move by
rounding alone. A number that is not finite is reported as None and fails.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by rounding alone and is not compared
STILL_LEAF = 1e-3

#: the per-client distributions the program's telemetry sketches
SOURCES = ("d_norm", "drift")

#: the quantiles a sketch reports besides its top-k (``max`` is the 1.0)
QUANTILES = (0.5, 0.9, 0.99, 1.0)


def _finite(v: float):
    return float(v) if math.isfinite(v) else None


def sketch_spec(telemetry: str) -> dict:
    """Histogram bins, log10 range and top-k of a telemetry spec string
    such as ``memory,hist:48:-12:4,topk:4``; the range has to be stated."""
    spec = {}
    for part in telemetry.split(","):
        kind, _, arg = part.strip().partition(":")
        if kind == "hist":
            bins, lo, hi = arg.split(":")
            spec.update(bins=int(bins), lo=float(lo), hi=float(hi))
        elif kind == "topk":
            spec["k"] = int(arg)
    if set(spec) != {"bins", "lo", "hi", "k"}:
        raise ValueError(f"{telemetry!r} must state hist:<bins>:<lo>:<hi> "
                         f"and topk:<k>")
    return spec


def bins_of(values, spec: dict) -> np.ndarray:
    """Log10 bin of each value, as the program bins them: zeros and
    underflow into bin 0, overflow into the last."""
    v = np.asarray(values, np.float64)
    logs = np.where(v > 0, np.log10(np.where(v > 0, v, 1.0)), spec["lo"])
    idx = np.floor((logs - spec["lo"]) * (spec["bins"] / (spec["hi"]
                                                          - spec["lo"])))
    return np.clip(idx, 0, spec["bins"] - 1).astype(np.int64)


def sketch(norms, spec: dict) -> dict:
    """The sketch of per-round client norms ``[rounds, clients]`` in the
    layout ``numbers`` reads: per round the histogram, the quantiles and
    the top-k values and ids."""
    norms = np.asarray(norms, np.float64)
    k = min(spec["k"], norms.shape[1])
    order = np.argsort(-norms, axis=1, kind="stable")[:, :k]
    hist = np.stack([np.bincount(bins_of(r, spec), minlength=spec["bins"])
                     for r in norms])
    return {"hist": hist, "q": np.quantile(norms, QUANTILES, axis=1).T,
            "top_vals": np.take_along_axis(norms, order, 1),
            "top_ids": order}


def sketch_of_events(events, spec: dict) -> dict:
    """Per source, the program's sketches from its round events."""
    rounds = [e for e in events if e["event"] == "round"]
    names = ("p50", "p90", "p99", "max")
    return {src: {
        "hist": np.asarray([e[f"{src}_hist"] for e in rounds]),
        "q": np.asarray([[e[f"{src}_{q}"] for q in names] for e in rounds]),
        "top_vals": np.asarray([e[f"{src}_top_vals"] for e in rounds]),
        "top_ids": np.asarray([e[f"{src}_top_ids"] for e in rounds])}
        for src in SOURCES}


def _sketch_gap(sk: dict, norms: np.ndarray) -> float:
    n = norms.shape[1]
    ids = np.asarray(sk["top_ids"])
    if (ids.min() < 0 or ids.max() >= n
            or any(len(set(r)) < len(r) for r in ids.tolist())):
        return math.inf
    named = np.take_along_axis(norms, ids, 1)
    q = np.quantile(norms, QUANTILES, axis=1).T
    return max(np.max(np.abs(sk["top_vals"] - named) / named),
               np.max(np.abs(sk["q"] - q) / q))


def _hist_moved(sk: dict, norms: np.ndarray, spec: dict) -> int:
    """Clients held in a bin that neither their reference norm nor the
    program's reported norm of them falls in, summed over rounds."""
    moved = 0
    for hist, ids, vals, ref in zip(sk["hist"], sk["top_ids"],
                                    sk["top_vals"], norms):
        ref_bins = bins_of(ref, spec)
        own = ref_bins.copy()
        own[np.asarray(ids)] = bins_of(vals, spec)
        either = [sorted({int(a), int(b)}) for a, b in zip(ref_bins, own)]
        moved += min(
            int(np.sum(np.abs(np.asarray(hist) - np.bincount(
                pick, minlength=spec["bins"])))) // 2
            for pick in itertools.product(*either))
    return moved


def numbers(prog: dict, ref: dict, spec: dict) -> dict:
    def rel(name):
        p, r = np.asarray(prog[name], np.float64), np.asarray(ref[name],
                                                              np.float64)
        return np.max(np.abs(p - r) / np.abs(r))

    g0 = np.asarray(ref["g0"], np.float64)
    keep = g0 >= STILL_LEAF * np.median(g0)

    def leaf(name):
        p = np.asarray(prog[name], np.float64)[keep]
        r = np.asarray(ref[name], np.float64)[keep]
        return np.max(np.abs(p - r) / np.maximum(r, np.median(r)))

    norms = {s: np.asarray(ref[s], np.float64) for s in SOURCES}
    sketch_gap = _finite(max(_sketch_gap(prog["sketch"][s], norms[s])
                             for s in SOURCES))
    return {"loss_gap": _finite(rel("loss")),
            "grad_norm_gap": _finite(rel("grad_norm")),
            "dx_gap": _finite(leaf("dx")), "d_gap": _finite(leaf("d")),
            "sketch_gap": sketch_gap,
            "hist_moved": None if sketch_gap is None else sum(
                _hist_moved(prog["sketch"][s], norms[s], spec)
                for s in SOURCES)}


def as_program(ref: dict, spec: dict) -> dict:
    """A reference's result in the program's layout, its sketches made from
    its own client norms: how a reference stands in the program's place."""
    return dict(ref, sketch={s: sketch(ref[s], spec) for s in SOURCES})


def judge(values: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number the limits file holds."""
    return {name: {"value": values[name], "limit": limit}
            for name, limit in limits.items()}
