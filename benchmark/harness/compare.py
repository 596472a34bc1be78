"""The comparison that decides ``correct``: the timed call's output at a
fixed round against the plain reference's, number by number.

Both sides are reduced to the same record (``test_loss`` and
``client_loss`` per followed round, and the change of every parameter
tensor from the shared starting weights to the last followed round), so
the reference in a lower precision, or with a fault planted, can stand
in the program's place through the same code.
"""

from __future__ import annotations

import statistics

import numpy as np


def flatten(tree, prefix=()) -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flatten(v, prefix + (k,)))
        else:
            out[prefix + (k,)] = np.asarray(v, np.float32)
    return out


def deltas(params, params0) -> dict:
    a, b = flatten(params), flatten(params0)
    if set(a) != set(b):
        raise ValueError(
            "parameter trees differ: "
            f"{sorted(set(a) ^ set(b))[:4]} ..."
        )
    return {k: a[k].astype(np.float64) - b[k] for k in a}


def record(test_loss, client_loss, params, params0, groups=None) -> dict:
    """``groups`` (the reference's side only): ``(sums, weights, subsets)``
    of the last round's weighted parameter sums over disjoint groups of
    clients, and the named subsets made of those groups, from which
    :func:`client_share_gap` reads whether the other side's mean was
    taken over one of those subsets alone."""
    out = {
        "test_loss": [float(v) for v in test_loss],
        "client_loss": [float(v) for v in client_loss],
        "delta": deltas(params, params0),
    }
    if groups is not None:
        sums, weights, subsets = groups
        base = flatten(params0)
        flat = [flatten(s) for s in sums]
        out["subset_delta"] = {}
        for name, members in subsets.items():
            weight = sum(weights[g] for g in members)
            out["subset_delta"][name] = {
                k: sum(flat[g][k].astype(np.float64) for g in members)
                / weight - base[k]
                for k in base
            }
    return out


def _dot(a: dict, b: dict) -> float:
    return float(sum(np.vdot(a[k], b[k]) for k in a))


def client_share_gap(delta: dict, full: dict, subset_delta: dict) -> float:
    """How much of the signature of "only some clients were counted" the
    change ``delta`` carries. For each named subset of clients ``d`` is
    the subset's own mean change minus the full mean ``full``: the error
    a mean over that subset alone would make. The number is the largest
    share of such a ``d`` found in ``delta - full`` (projection over
    ``|d|^2``). It reads exactly 1 when the change is the mean over one
    of those subsets (the other half of the clients left out; one chip's
    shard standing for all), and near 0 for a sound run: ``d`` is
    clients' sampling noise, a few percent of the change. It is a
    matched filter: it sees the subsets it is given (the reference names
    them) and no others."""
    err = {k: delta[k] - full[k] for k in full}
    worst = 0.0
    for part in subset_delta.values():
        d = {k: part[k] - full[k] for k in full}
        worst = max(worst, abs(_dot(err, d)) / _dot(d, d))
    return float(worst)


def numbers(got: dict, ref: dict) -> dict:
    """Every number compared, each a relative gap (0 = equal).

    * ``test_loss_r<i>`` / ``client_loss_r<i>``: each followed round's
      server test loss and mean client training loss.
    * ``update_norm``: the norm of the whole parameter change.
    * ``update_norm_worst_leaf``: the worst tensor's gap between the two
      norms of its change, against the reference's norm of that tensor
      or of the median tensor, whichever is larger. Tensors the
      reference moves by under a thousandth of the median are left out
      (nothing to compare but round-off).
    * ``update_direction``: the norm of the difference of the two whole
      changes against the reference's norm; unlike the norms it sees an
      update of the right size that points elsewhere.
    * ``client_share_gap``: :func:`client_share_gap` of the change.
    """
    out = {}
    for name in ("test_loss", "client_loss"):
        if len(got[name]) != len(ref[name]):
            raise ValueError(f"{name}: {len(got[name])} rounds against "
                             f"{len(ref[name])}")
        for i, (g, r) in enumerate(zip(got[name], ref[name])):
            out[f"{name}_r{i}"] = abs(g - r) / abs(r)
    ref_norm = {k: float(np.linalg.norm(v)) for k, v in ref["delta"].items()}
    got_norm = {k: float(np.linalg.norm(got["delta"][k])) for k in ref_norm}
    median = statistics.median(ref_norm.values())
    worst = 0.0
    for k, r in ref_norm.items():
        if r < 1e-3 * median:
            continue
        worst = max(worst, abs(got_norm[k] - r) / max(r, median))
    total_ref = float(np.sqrt(sum(v * v for v in ref_norm.values())))
    total_got = float(np.sqrt(sum(v * v for v in got_norm.values())))
    diff = float(np.sqrt(sum(
        float(np.sum(np.square(got["delta"][k] - ref["delta"][k])))
        for k in ref_norm
    )))
    out["update_norm"] = abs(total_got - total_ref) / total_ref
    out["update_norm_worst_leaf"] = worst
    out["update_direction"] = diff / total_ref
    if "subset_delta" in ref:
        out["client_share_gap"] = client_share_gap(
            got["delta"], ref["delta"], ref["subset_delta"]
        )
    return out


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the table printed with every run: each number held
    to a limit beside that limit. A number that is not finite fails."""
    table = {}
    ok = True
    for name, limit in limits.items():
        value = nums.get(name)
        if value is None:
            raise KeyError(f"limit on {name!r}, which is not compared")
        table[name] = {"value": value, "limit": limit}
        if not (np.isfinite(value) and value <= limit):
            ok = False
    return ok, table


def subset_record(ref: dict, subset: str) -> dict:
    """The reference's record with the last round's mean taken over the
    named subset of clients alone (the rest left out): a planted fault."""
    return {**ref, "delta": ref["subset_delta"][subset]}


def unchanged_record(ref: dict, start_loss: float) -> dict:
    """The record of a program that hands its state back unchanged: no
    parameter moves and the test loss stays where it started."""
    return {
        **ref,
        "test_loss": [float(start_loss)] * len(ref["test_loss"]),
        "delta": {k: np.zeros_like(v) for k, v in ref["delta"].items()},
    }
