"""Plain references for the benchmark's correctness checks.

Straightforward ``jax.numpy`` with no kernels, no sparse tricks and
nothing imported from the program: the relation-wise GNN of Graph4Rec
Eq. 3 (a per-relation layer over each relation's sampled neighbours, a
mix over relations and an ``alpha`` residual to the hop-0 embedding), the
in-batch softmax loss, and the optimizer the configuration states
(row-wise AdaGrad on the embedding table, Adam on the dense weights),
applied to the full table. The layer and the mix are the configuration's
encoder modules (``harness.encoder``: ``encoders/<gnn_type>.py`` and
``mixes/<relation_agg>.py``); everything else here is shared by every
encoder. ``dtype`` float32 runs under ``highest`` matmul precision,
float32 as the configurations state it. The controls are the same
computation one step below: float32 at ``high`` (three bfloat16 passes)
or ``default`` (one pass, JAX's own on a TPU), and bfloat16 throughout.

Also here: the benchmark's own weight initialisation (one jitted call from
the seed, made on the device), the check that a sampled batch lies in the
graph, and exact top-k retrieval in item blocks.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# ----------------------------------------------------------------- weights
def param_specs(model: Dict, enc, num_nodes: int
                 ) -> Dict[str, Tuple[Tuple[int, ...], Optional[float]]]:
    """Every leaf, named as the program names it, with its shape and the
    scale of its N(0, 1) draw: the table (scaled in ``make_params``), the
    layer's leaves for each layer and relation, and the mix's leaves."""
    d = int(model["dim"])
    out = {"emb/node": ((num_nodes, d), None)}
    for layer in range(int(model["num_layers"])):
        for r in range(len(model["relations"])):
            for name, spec in enc.layer.leaves(d).items():
                out[f"gnn/l{layer}/r{r}/{name}"] = spec
    for name, spec in enc.mix.leaves(d).items():
        out[f"gnn/{name}"] = spec
    return out


def make_params(key: jax.Array, model: Dict, enc, num_nodes: int) -> Dict:
    """Weights from the seed, on the device, in one jitted call: one key
    per leaf in the order of the leaves' names, the table N(0, 1/d) per
    entry, every other leaf at its module's scale."""
    leaves = param_specs(model, enc, num_nodes)
    d = int(model["dim"])

    def init(key):
        out = {}
        keys = jax.random.split(key, len(leaves))
        for k, (name, (shape, scale)) in zip(keys, sorted(leaves.items())):
            z = jax.random.normal(k, shape, jnp.float32)
            out[name] = z / np.sqrt(d) if scale is None else z * scale
        return out

    return jax.jit(init)(key)


# ------------------------------------------------------------------- model
def _embed(table, ids):
    rows = table[jnp.maximum(ids, 0)]
    return jnp.where((ids >= 0)[..., None], rows, jnp.zeros((), table.dtype))


def encode(params, model: Dict, enc, levels: Sequence[jnp.ndarray]
           ) -> jnp.ndarray:
    """Towers (level k: (B, W_k) global ids, PAD -1) -> (B, d)."""
    fan = [int(f) for f in model["fanouts"]]
    R = len(model["relations"])
    alpha = float(model["alpha"])
    d = int(model["dim"])
    mix = {n: params["gnn/" + n] for n in enc.mix.leaves(d)}
    h = [_embed(params["emb/node"], l) for l in levels]
    h0, masks = list(h), [l >= 0 for l in levels]
    for layer in range(len(fan)):
        per_rel = [{n: params[f"gnn/l{layer}/r{r}/{n}"]
                    for n in enc.layer.leaves(d)} for r in range(R)]
        nxt = []
        for k in range(len(fan) - layer):
            B, W, _ = h[k].shape
            child = h[k + 1].reshape(B, W, R, fan[k], d)
            cmask = masks[k + 1].reshape(B, W, R, fan[k])
            rel = [
                enc.layer.forward(per_rel[r], h[k], child[:, :, r],
                                  cmask[:, :, r])
                for r in range(R)
            ]
            mixed = enc.mix.forward(mix, rel)
            out = alpha * h0[k] + (1 - alpha) * mixed
            nxt.append(out * masks[k][..., None].astype(out.dtype))
        h = nxt
    return h[0][:, 0, :]


def loss(params, model: Dict, enc, batch: Dict) -> jnp.ndarray:
    """In-batch softmax over the pairs (src_sel[p], dst_sel[p]) of towers."""
    h = encode(params, model, enc, batch["towers"])
    hs, hd = h[batch["src_sel"]], h[batch["dst_sel"]]
    logits = hs @ hd.T / float(model.get("temperature", 1.0))
    mx = logits.max(-1, keepdims=True)
    lse = jnp.log(jnp.exp(logits - mx).sum(-1)) + mx[:, 0]
    return (lse - jnp.diagonal(logits)).mean()


def _precision(dtype, precision="highest"):
    if dtype == jnp.float32:
        return jax.default_matmul_precision(precision)
    return contextlib.nullcontext()


def train_steps(params0: Dict, model: Dict, enc, opt: Dict,
                batches: List[Dict], dtype=jnp.float32,
                precision: str = "highest") -> Dict:
    """The configuration's training steps over ``batches`` from
    ``params0``. Returns per-step losses, the first gradient's norm per
    leaf, and the norm of each leaf's change after the last step. float32
    runs at ``precision``; another ``dtype`` at its default."""
    lr, dlr = float(opt["sparse_lr"]), float(opt["dense_lr"])
    acc0, eps = float(opt["adagrad_init_accum"]), 1e-8

    def step(p, acc, mu, nu, t, batch):
        lv, g = jax.value_and_grad(loss)(p, model, enc, batch)
        new = {}
        for k, v in p.items():
            if k.startswith("emb/"):
                a = acc[k] + jnp.mean(g[k] * g[k], -1, keepdims=True)
                new[k] = v - lr * g[k] / (jnp.sqrt(a) + eps)
                acc = {**acc, k: a}
            else:
                m = ADAM_B1 * mu[k] + (1 - ADAM_B1) * g[k]
                s = ADAM_B2 * nu[k] + (1 - ADAM_B2) * g[k] * g[k]
                mh = m / (1 - ADAM_B1 ** t)
                sh = s / (1 - ADAM_B2 ** t)
                new[k] = v - dlr * mh / (jnp.sqrt(sh) + eps)
                mu, nu = {**mu, k: m}, {**nu, k: s}
        gn = {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
              for k, x in g.items()}
        return new, acc, mu, nu, lv, gn

    with _precision(dtype, precision):
        p = {k: v.astype(dtype) for k, v in params0.items()}
        acc = {k: jnp.full((v.shape[0], 1), acc0, dtype)
               for k, v in p.items() if k.startswith("emb/")}
        mu = {k: jnp.zeros_like(v) for k, v in p.items()
              if not k.startswith("emb/")}
        nu = dict(mu)
        jstep = jax.jit(step, static_argnums=(4,))
        losses, gnorm = [], None
        for t, b in enumerate(batches, start=1):
            dev = jax.device_put(b)
            p, acc, mu, nu, lv, gn = jstep(p, acc, mu, nu, t, dev)
            losses.append(float(lv))
            if gnorm is None:
                gnorm = {k: float(v) for k, v in gn.items()}
        change = jax.jit(lambda a, b: {
            k: jnp.sqrt(jnp.sum(jnp.square(a[k].astype(jnp.float32)
                                           - b[k].astype(jnp.float32))))
            for k in a})(p, params0)
    return {"losses": losses, "grad_norms": gnorm,
            "change_norms": {k: float(v) for k, v in change.items()}}


def compare_training(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three compared numbers, each the worst over steps or leaves.

    ``loss``: largest relative gap of a step's loss. ``grad_norm`` and
    ``change_norm``: per leaf, the gap between the two norms over the
    larger of the reference's norm of that leaf and of the median leaf.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's are left out of ``change_norm``: Adam moves them by round-off.
    """
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(prog["losses"], ref["losses"]))
    out = {"loss": loss_gap}
    gmed = float(np.median(list(ref["grad_norms"].values())))
    for key in ("grad_norm", "change_norm"):
        r, p = ref[key + "s"], prog[key + "s"]
        med = float(np.median(list(r.values())))
        gaps = [
            abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in r
            if key == "grad_norm" or ref["grad_norms"][k] >= 1e-3 * gmed
        ]
        out[key] = max(gaps)
    return out


# ------------------------------------------------------- batch validation
def _member(indptr, indices, rows, vals) -> np.ndarray:
    """For each (row, val): is val among CSR row ``row``'s neighbours?"""
    rows = np.asarray(rows, np.int64)
    vals = np.asarray(vals, np.int64)
    out = np.zeros(len(rows), bool)
    for i, (r, v) in enumerate(zip(rows, vals)):
        out[i] = bool((indices[indptr[r]:indptr[r + 1]] == v).any())
    return out


def invalid_in_batch(batch: Dict, graph: Dict, model: Dict,
                     pair_relations: Sequence[str]) -> int:
    """Count of sampled entries that do not lie in the graph.

    Ego children: a child under relation r must be a neighbour of its
    parent under r, and PAD exactly where the parent is PAD or has no
    neighbour under r. Pairs: endpoints within two hops of a walk, i.e.
    adjacent, or sharing a neighbour, under one of the walk relations.
    """
    fan = [int(f) for f in model["fanouts"]]
    rels = list(model["relations"])
    R = len(rels)
    bad = 0
    levels = [np.asarray(l) for l in batch["towers"]]
    for k in range(len(fan)):
        par = np.repeat(levels[k], R * fan[k], axis=1).reshape(-1)
        rel_of = np.tile(np.repeat(np.arange(R), fan[k]),
                         levels[k].size).reshape(-1)
        child = levels[k + 1].reshape(-1)
        for r, name in enumerate(rels):
            indptr, indices = graph[name]
            sel = rel_of == r
            p, c = par[sel], child[sel]
            deg = np.where(p >= 0, indptr[np.maximum(p, 0) + 1]
                           - indptr[np.maximum(p, 0)], 0)
            want_pad = deg == 0
            bad += int(((c >= 0) == want_pad).sum())
            live = (c >= 0) & ~want_pad
            bad += int((~_member(indptr, indices, p[live], c[live])).sum())
    centers = levels[0][:, 0]
    src = centers[np.asarray(batch["src_sel"])]
    dst = centers[np.asarray(batch["dst_sel"])]
    for s, t in zip(src, dst):
        if s < 0 or t < 0:
            bad += 1
            continue
        ok = False
        for name in pair_relations:
            indptr, indices = graph[name]
            ns = indices[indptr[s]:indptr[s + 1]]
            if (ns == t).any():
                ok = True
                break
            nt = indices[indptr[t]:indptr[t + 1]]
            if ns.size and nt.size and np.intersect1d(ns, nt).size:
                ok = True
                break
        bad += 0 if ok else 1
    return bad


# ---------------------------------------------------------------- retrieval
def exact_topk(queries: np.ndarray, items, exclude: np.ndarray, k: int,
               dtype=jnp.float32, precision: str = "highest",
               block: int = 1 << 19):
    """Top-k (scores, ids) over the whole (I, d) device table, in item
    blocks, with each query's ``exclude`` ids (PAD -1) scored -inf. f32 runs
    at ``precision``; another ``dtype`` at its default."""
    Q, I = queries.shape[0], items.shape[0]
    with _precision(dtype, precision):
        q = jax.device_put(queries).astype(dtype)

        @jax.jit
        def block_topk(qb, it, lo, exb):
            nb = it.shape[0]
            s = (qb @ it.astype(dtype).T).astype(jnp.float32)
            s = jnp.where(lo + jnp.arange(nb)[None, :] < I, s, -jnp.inf)
            col = jnp.where((exb >= lo) & (exb < lo + nb), exb - lo, nb)
            rows = jnp.arange(qb.shape[0])[:, None]
            s = s.at[rows, col].set(-jnp.inf, mode="drop")
            v, i = jax.lax.top_k(s, k)
            return v, i + lo

        best_s = np.full((Q, k), -np.inf, np.float32)
        best_i = np.full((Q, k), -1, np.int64)
        exd = jax.device_put(np.asarray(exclude, np.int32))
        for lo in range(0, I, block):
            it = items[lo:lo + block]
            if it.shape[0] < block:  # one shape for every block
                it = jnp.pad(it, ((0, block - it.shape[0]), (0, 0)))
            v, i = (np.asarray(x) for x in block_topk(q, it, lo, exd))
            s = np.concatenate([best_s, v], 1)
            ids = np.concatenate([best_i, i], 1)
            order = np.argsort(-s, axis=1, kind="stable")[:, :k]
            best_s = np.take_along_axis(s, order, 1)
            best_i = np.take_along_axis(ids, order, 1)
    return best_s, np.where(np.isfinite(best_s), best_i, -1)


def topk_reference(queries: np.ndarray, items, exclude: np.ndarray, k: int,
                   returned: np.ndarray) -> Dict[str, float]:
    """The readings of the ``returned`` (Q, k) ids against exact top-k.

    ``score_gap``: the widest gap by which a returned item's exact score
    lies below the k-th best exact score of its query. ``invalid``: slots
    that are empty, repeated within a row, or excluded for the query.
    """
    ex = np.asarray(exclude, np.int64)
    best, _ = exact_topk(queries, items, exclude, k)
    ids = np.asarray(returned, np.int64)
    with _precision(jnp.float32):
        got = np.asarray(jax.jit(lambda qq, rows: jnp.einsum(
            "qd,qkd->qk", qq, rows))(jax.device_put(queries),
                                    items[np.maximum(ids, 0)]))
    kth = best[:, -1:]
    live = ids >= 0
    gap = float(np.max(np.where(live, kth - got, 0.0)))
    invalid = int((~live).sum())
    for row, exr in zip(ids, ex):
        r = row[row >= 0]
        invalid += int(len(r) - len(np.unique(r)))
        invalid += int(np.isin(r, exr[exr >= 0]).sum())
    return {"score_gap": max(gap, 0.0), "invalid": invalid}
