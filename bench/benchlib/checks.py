"""The comparisons that decide ``correct``.

Each compared number has a limit of its own, from the configuration's
``limits``; a run is correct when every number is at or under its limit.
The reference (``bench/references/<name>.py``) recomputes what the timed
path produced, at the timed sizes, after the window has closed.

Fit states are read through Algorithm 2's ring layout only: every center
owns W slots, an empty slot has coefficient 0, and each iteration decays a
center's coefficients by (1 - alpha) and appends the b_j batch points
assigned to it, each with coefficient alpha / b_j, at its ring head.  With
the paper's rate alpha = sqrt(b_j / b), a center's newest points carry
1 / sqrt(b_j b) exactly.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# A newest run is undecayed when its coefficient times sqrt(n b) is 1 to
# within this (f32 rounding gives about 1e-7); a run decayed even once
# reads at most 1 - 1/sqrt(b), below 1 - 1/64 for every b up to 4096.
UNDECAYED_TOL = 1e-3


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit

    def line(self) -> str:
        return f"{self.name}={self.value!r} limit={self.limit!r}"


def compare(values: dict, limits: dict) -> list:
    """Compared numbers in a stable order; a number with no limit is an
    error in the configuration, never a pass."""
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the configuration")
    return [Compared(k, float(values[k]), float(limits[k]))
            for k in sorted(values)]


def compact(x, idx, coef, align: int = 128):
    """(pts (k, A, d) on the device, coef (k, A) f32) over the active
    slots of each center, A the most any center holds rounded up to
    ``align`` (padding carries coefficient 0)."""
    import jax.numpy as jnp

    active = coef != 0
    a = max(int(active.sum(axis=1).max()), 1)
    a += -a % align
    k = coef.shape[0]
    cidx = np.zeros((k, a), np.int32)
    ccoef = np.zeros((k, a), np.float32)
    for j in range(k):
        pos = np.flatnonzero(active[j])
        cidx[j, :pos.size] = idx[j, pos]
        ccoef[j, :pos.size] = coef[j, pos]
    pts = x[jnp.asarray(cidx.reshape(-1))].reshape(k, a, -1)
    return pts, jnp.asarray(ccoef)


def newest_run(coef_row, head: int):
    """Ring positions of the newest run of equal coefficients before
    ``head`` (newest first)."""
    w = coef_row.shape[0]
    pos = (head - 1) % w
    c = coef_row[pos]
    if c == 0:
        return []
    run = []
    while len(run) < w and coef_row[pos] == c:
        run.append(pos)
        pos = (pos - 1) % w
    return run


def last_batch(key, iters: int, n: int, b: int) -> np.ndarray:
    """Row ids of the last iteration's batch of a fit called with ``key``:
    the derivation that ``repro.api.keys`` documents (copied here, not
    imported) — the root key splits into (init key, fit key), each
    iteration splits the fit key into (next fit key, batch key), and a
    batch is b ids uniform on [0, n)."""
    import jax
    import jax.numpy as jnp

    _, fit_key = jax.random.split(key)
    kb = None
    for _ in range(iters):
        fit_key, kb = jax.random.split(fit_key)
    return np.asarray(jax.random.randint(kb, (b,), 0, n, dtype=jnp.int32))


def last_runs(idx, coef, head, b: int, batch) -> tuple:
    """(rate readings, {center: ring positions}) of the runs appended in
    the last iteration.  A center's newest run is undecayed
    (c sqrt(n b) = 1) when it took points in the last iteration, and also
    when it has taken none since its newest run: such an idle run holds
    points of an older batch.  A run is the last iteration's when its ids
    all belong to the last batch and one of them to no other undecayed
    run."""
    from collections import Counter

    rate, cand = [], {}
    for j in range(coef.shape[0]):
        run = newest_run(coef[j], int(head[j]))
        if not run:
            continue
        r = abs(float(coef[j, run[0]]) * math.sqrt(len(run) * b) - 1.0)
        rate.append(r)
        if r <= UNDECAYED_TOL:
            cand[j] = run
    in_batch = Counter(batch.tolist())
    claims = Counter(p for run_j, run in cand.items()
                     for p in set(idx[run_j, run].tolist()))
    last = {}
    for j, run in cand.items():
        ids = Counter(idx[j, run].tolist())
        if all(in_batch[p] >= c for p, c in ids.items()) and any(
                claims[p] == 1 for p in ids):
            last[j] = run
    return rate, last


def fit_numbers(state: dict, iters: int, key, x, config: dict, ref,
                control: bool = False, log=None) -> dict:
    """Numbers compared for one fitted state (host numpy arrays ``idx``,
    ``coef``, ``head``, ``sqnorm``, ``counts``) after ``iters`` iterations
    of a fit called with ``key``, each against the reference at its
    highest precision:

    * ``counts_err``: |sum of assigned counts - iters * b|: every
      iteration assigns all b batch points;
    * ``slots_err``: |active slots - sum_j min(W, 1 + counts_j)|: the ring
      holds every appended point, up to its size;
    * ``rate_err``: median over centers of |c sqrt(n b) - 1| for the
      newest run of n points with coefficient c: the rate and the append
      (a center decays only when it takes points, so its newest run keeps
      alpha / n = 1 / sqrt(n b) until then);
    * ``batch_err``: |points of the last iteration's runs - b|: every
      point of the last batch was appended somewhere;
    * ``member_gap``: the centers before the last iteration are rebuilt
      by undoing its append and decay; over the last batch's points, the
      widest gap between the reference distance to the center each was
      appended to and to the nearest center: the assignment;
    * ``sqnorm_rel``: the widest gap between the state's <C_j, C_j> and
      the reference's, over the larger of that center's and the median
      center's reference norm: the recompute;
    * ``stop_gap``: for a fit that stopped before ``max_iters``, how far
      the reference's improvement of the batch objective in the last
      iteration, f_B(before) - f_B(after) on the rebuilt and the final
      centers, lies above ``epsilon``; 0 for a fit that ran to
      ``max_iters``: the objective, whose only output is the early stop.
      The reference's improvement is passed to ``log`` either way.

    With ``control`` the reference at its lower precision stands in the
    program's place for what the program computed: the appended
    coefficient, each last-batch point's center, and <C_j, C_j>.
    """
    import jax.numpy as jnp

    low = "float8"

    idx, coef = state["idx"], state["coef"]
    counts = state["counts"]
    k, w = coef.shape
    b = int(config["batch_size"])
    kappa = float(config["kappa"])
    counts_i = np.rint(counts).astype(np.int64)
    out = {"counts_err": abs(int(counts_i.sum()) - iters * b),
           "slots_err": abs(int((coef != 0).sum())
                            - int(np.minimum(w, 1 + counts_i).sum()))}

    batch = last_batch(key, iters, x.shape[0], b)
    rate, last = last_runs(idx, coef, state["head"], b, batch)
    if control:
        rate = [abs(float(ref.operand(coef[j, run[0]], low))
                    * math.sqrt(len(run) * b) - 1.0)
                for j, run in last.items()]
    out["rate_err"] = float(np.median(rate)) if rate else math.inf
    prev = coef.astype(np.float64)
    members, owner = [], []
    for j, run in last.items():
        members.extend(idx[j, run])
        owner.extend([j] * len(run))
        prev[j, run] = 0.0
        prev[j] /= 1.0 - math.sqrt(len(run) / b)
    out["batch_err"] = abs(len(members) - b)

    out["member_gap"] = math.inf
    xb = x[jnp.asarray(batch)]
    pts, c = compact(x, idx, prev.astype(np.float32))
    f_before = batch_objective(xb, pts, c, kappa, ref)
    if members:
        xq = x[jnp.asarray(np.asarray(members, np.int32))]
        if control:
            owner = np.asarray(ref.nearest(xq, pts, c, kappa, low))
        out["member_gap"] = label_gap(xq, owner, pts, c, kappa, ref)

    pts, c = compact(x, idx, coef)
    norms = np.asarray(ref.center_norms(pts, c, kappa))
    got = state["sqnorm"]
    if control:
        got = np.asarray(ref.center_norms(pts, c, kappa, low))
    scale = np.maximum(norms, np.median(norms))
    out["sqnorm_rel"] = float(np.max(np.abs(got - norms) / scale))

    improvement = f_before - batch_objective(xb, pts, c, kappa, ref, norms)
    stopped = iters < int(config["max_iters"])
    out["stop_gap"] = (max(0.0, improvement - float(config["epsilon"]))
                       if stopped else 0.0)
    if log is not None:
        log(f"iters={iters} last_improvement={improvement!r}")
    return out


def batch_objective(xb, pts, coef, kappa, ref, norms=None) -> float:
    """f_B(C): the mean over the batch rows of the reference distance to
    the nearest center."""
    if norms is None:
        norms = ref.center_norms(pts, coef, kappa)
    d = ref.distances(xb, pts, coef, norms, kappa)
    return float(np.asarray(d.min(axis=1), np.float64).mean())


def label_gap(xq, labels, pts, coef, kappa, ref, norms=None) -> float:
    """The widest gap by which a label's reference distance lies above the
    reference's nearest center (``norms``: the reference's <C_j, C_j>,
    where already computed)."""
    if norms is None:
        norms = ref.center_norms(pts, coef, kappa)
    d = np.asarray(ref.distances(xq, pts, coef, norms, kappa))
    labels = np.asarray(labels)
    if labels.min() < 0 or labels.max() >= d.shape[1]:
        return math.inf
    return float((d[np.arange(d.shape[0]), labels] - d.min(axis=1)).max())


def worst(rows: list) -> dict:
    """Per number, the worst (largest) reading over several answers."""
    return {key: max(float(r[key]) for r in rows) for key in rows[0]}
