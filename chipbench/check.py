"""What decides ``correct``: readings of the state that the timed round
produced over the first check steps, against the same readings of the
plain reference (`chipbench.reference`).

Readings, taken in float32 from the node-stacked state after each check
step t: the hypergradient's norm |mean_i u_i| and the consensus errors of
x, y and z (sum_i |v_i - mean v|^2); after step 1, the norm of each leaf
of the gradients the optimizer holds (u for x, the y and z loops' last
gradients) and the compressor's survivors of the round: the elements
that the s-messages of the y and z loops changed in their reference
points s_hat (the union of the K steps' kept entries, per node and
leaf); after the last step, the norm of each leaf's change since the
start for x, y, z and the y and z loops' reference points.

Numbers (each a relative gap, the worst over what it covers); a cell's
limit file (`chipbench/workloads/<cell>.json`) says which decide:

* ``change_x_gap``: |c_p - c_r| / max(c_r, median leaf c_r) over the
  leaves of the upper variable x, c = |x_T - x_0|, leaving out leaves
  whose reference gradient is under a thousandth of the median leaf's
  (they move by round-off alone);
* ``change_x_median_gap``: the median of those leaf gaps;
* ``change_yz_gap``: the same as ``change_x_gap`` over y, z and their
  reference points;
* ``consensus_y1_gap``: |e_p - e_r| / e_r of y's consensus error after
  step 1;
* ``consensus_gap``: the same over x, y, z and every step;
* ``hypergrad_gap``: |hg_p - hg_r| / hg_r over the steps;
* ``hypergrad1_gap``: the same at step 1;
* ``grad1_gap``: |n_p - n_r| / max(n_r, median leaf n_r) over the leaves
  of the gradients after step 1;
* ``survivors_gap``: the entries kept by one side and not the other,
  over the entries the reference kept (|S_p xor S_r| / |S_r|), in step 1,
  the worse of the y and z loops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

GRAD_FLOOR = 1e-3
GRAD_OF = {"x": "u", "y": "gy", "z": "gz", "y_hat": "gy", "z_hat": "gz"}


def leaf_names(tree) -> list:
    paths, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(p) for p, _ in paths]


def _norm(v):
    v = v.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(v * v))


def _consensus(tree):
    tot = 0.0
    for v in jax.tree.leaves(tree):
        v = v.astype(jnp.float32)
        tot = tot + jnp.sum(jnp.square(v - jnp.mean(v, axis=0, keepdims=True)))
    return tot


@jax.jit
def step_reading(s):
    """``s``: {"x", "u", "y", "z", ...} node-stacked trees."""
    mean_u = jax.tree.map(lambda v: jnp.mean(v.astype(jnp.float32), axis=0), s["u"])
    return {
        "hg": jnp.sqrt(sum(jnp.sum(v * v) for v in jax.tree.leaves(mean_u))),
        "cx": _consensus(s["x"]), "cy": _consensus(s["y"]), "cz": _consensus(s["z"]),
    }


@jax.jit
def grad_reading(s):
    return {k: [_norm(v) for v in jax.tree.leaves(s[k])] for k in ("u", "gy", "gz")}


def change_reading(s, mc: dict, key, m: int):
    @jax.jit
    def fn(s, key):
        x0, y0 = weights.make_xy(mc, key, m)
        start = {"x": x0, "y": y0, "z": y0, "y_hat": y0, "z_hat": y0}
        return {k: [_norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                    for a, b in zip(jax.tree.leaves(s[k]), jax.tree.leaves(v))]
                for k, v in start.items()}

    return fn({k: s[k] for k in GRAD_OF}, key)


def hat_view(s) -> dict:
    """The s-messages' reference points of the y and z loops, on the host."""
    return jax.device_get({"y": s["s_hat_y"], "z": s["s_hat_z"]})


def _bits(v):
    v = np.asarray(v)
    return v.view(f"u{v.dtype.itemsize}")


def survivor_masks(before: dict, after: dict) -> dict:
    """Per loop, the packed mask of entries that changed (bit for bit),
    over the node-stacked leaves."""
    return {k: [np.packbits(_bits(a) != _bits(b))
                for a, b in zip(jax.tree.leaves(before[k]), jax.tree.leaves(after[k]))]
            for k in before}


class Readings:
    """Readings of one run (program or reference) over the check steps.
    ``hat0``: `hat_view` of the state before step 1."""

    def __init__(self, hat0: dict):
        self.hat0 = hat0
        self.steps = []
        self.grads = None
        self.changes = None
        self.survivors = None

    def take(self, s, t: int, last: int, mc: dict, key, m: int):
        self.steps.append(jax.device_get(step_reading(s)))
        if t == 1:
            self.grads = jax.device_get(grad_reading(s))
            self.survivors = survivor_masks(self.hat0, hat_view(s))
            self.hat0 = None
        if t == last:
            self.changes = jax.device_get(change_reading(s, mc, key, m))

    def as_dict(self):
        f = lambda d: {k: [float(x) for x in v] for k, v in d.items()}
        return {"steps": [{k: float(v) for k, v in st.items()} for st in self.steps],
                "grads": f(self.grads), "changes": f(self.changes),
                "survivors": self.survivors}


def program_view(state) -> dict:
    """The reading names of a `repro.core.c2dfb.C2DFBState`."""
    return {"x": state.x, "u": state.u_prev, "y": state.inner_y.d,
            "z": state.inner_z.d, "y_hat": state.inner_y.d_hat,
            "z_hat": state.inner_z.d_hat, "gy": state.inner_y.g_prev,
            "gz": state.inner_z.g_prev, "s_hat_y": state.inner_y.s_hat,
            "s_hat_z": state.inner_z.s_hat}


def reference_view(st: dict) -> dict:
    return {"x": st["x"], "u": st["u_prev"], "y": st["y"]["d"], "z": st["z"]["d"],
            "y_hat": st["y"]["d_hat"], "z_hat": st["z"]["d_hat"],
            "gy": st["y"]["g_prev"], "gz": st["z"]["g_prev"],
            "s_hat_y": st["y"]["s_hat"], "s_hat_z": st["z"]["s_hat"]}


def _rel(p, r, floor):
    gap = abs(p - r) / max(r, floor, 1e-30)
    return gap if np.isfinite(gap) else float("inf")


def _leaf_gaps(prog: dict, ref: dict, keys) -> list:
    out = []
    for k in keys:
        rn = ref["changes"][k]
        g = ref["grads"][GRAD_OF[k]]
        gmed = float(np.median(g))
        keep = [i for i in range(len(rn)) if g[i] >= GRAD_FLOOR * gmed]
        med = float(np.median([rn[i] for i in keep]))
        out += [(_rel(prog["changes"][k][i], rn[i], med), f"{k} leaf {i}")
                for i in keep]
    return out


def compare(prog: dict, ref: dict) -> dict:
    """Each number's relative gap of the program's readings
    (``Readings.as_dict``) against the reference's, with where its worst
    lies."""
    steps = list(enumerate(zip(prog["steps"], ref["steps"]), 1))
    out = {
        "change_x_gap": max(_leaf_gaps(prog, ref, ["x"])),
        "change_x_median_gap": (float(np.median([g for g, _ in _leaf_gaps(prog, ref, ["x"])])),
                                "median x leaf"),
        "change_yz_gap": max(_leaf_gaps(prog, ref, ["y", "z", "y_hat", "z_hat"])),
        "consensus_y1_gap": (_rel(steps[0][1][0]["cy"], steps[0][1][1]["cy"], 0.0),
                             "cy step 1"),
        "consensus_gap": max((_rel(p[k], r[k], 0.0), f"{k} step {t}")
                             for t, (p, r) in steps for k in ("cx", "cy", "cz")),
        "hypergrad_gap": max((_rel(p["hg"], r["hg"], 0.0), f"step {t}")
                             for t, (p, r) in steps),
        "hypergrad1_gap": (_rel(steps[0][1][0]["hg"], steps[0][1][1]["hg"], 0.0),
                           "step 1"),
    }
    grads = []
    for k, rn in ref["grads"].items():
        med = float(np.median(rn))
        grads += [(_rel(p, r, med), f"{k} leaf {i}")
                  for i, (p, r) in enumerate(zip(prog["grads"][k], rn))]
    out["grad1_gap"] = max(grads)
    out["survivors_gap"] = max(_survivor_gap(prog["survivors"][k], ref["survivors"][k], k)
                               for k in ref["survivors"])
    return out


_POPCOUNT = np.array([bin(i).count("1") for i in range(256)], np.uint8)


def _count(packed) -> int:
    return int(_POPCOUNT[packed].sum(dtype=np.int64))


def _survivor_gap(prog: list, ref: list, loop: str):
    differ = sum(_count(np.bitwise_xor(p, r)) for p, r in zip(prog, ref))
    kept = sum(_count(r) for r in ref)
    return differ / max(kept, 1), f"{loop} loop"


def verdict(gaps: dict, limits: dict) -> tuple[bool, dict]:
    """``limits``: number -> limit, for the numbers compared; the others
    are shown with the limit None and decide nothing."""
    shown, ok = {}, True
    for name, (value, where) in gaps.items():
        lim = limits.get(name)
        shown[name] = {"value": value, "limit": lim, "worst": where}
        if lim is not None and not value <= lim:
            ok = False
    if not any(limits.get(n) is not None for n in gaps):
        ok = False
    return ok, shown
