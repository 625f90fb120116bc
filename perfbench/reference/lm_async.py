"""Plain reference of OLAF-async LM training, independent of the program.

A dense Llama-style decoder (RMSNorm, half-split RoPE, grouped-query causal
attention, SwiGLU MLP, tied embeddings) written out in ``jax.numpy``
float32 at ``Precision.HIGHEST``, its cross-entropy loss and gradients,
and the parameter server it feeds:

* workers finish in the order of their next finish time, each worker's
  speed drawn once from the seed; each computes its gradient on its own
  shard of the synthetic token stream, at the parameters of the step;
* a burst of U updates enters a queue of Q slots by Algorithm 1 of the
  OLAF paper: an update whose cluster waits in the queue replaces it if
  it is an un-aggregated update of the same worker, else is averaged into
  it; otherwise it is appended, or dropped when the queue is full;
* the PS drains the k oldest slots and applies the mean of the raw
  gradients they carry, clipped to global norm 1, with Adam; the
  parameters are stored in the configuration's dtype after each step.

Weights and tokens are made from the seed by the same recipe the
configuration states (threefry normal draws, cast to the parameter dtype;
the counter-keyed Markov token stream), so both sides start from the same
numbers without the reference taking any array from the program.

``mode`` selects the arithmetic: ``"f32"`` (the reference), ``"fp8"``
(matmul operands rounded to float8_e4m3fn: the control), and
``"half_batch"`` (each worker's gradient over half its rows: a planted
fault).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Params = Dict[str, jnp.ndarray]


# ---------------------------------------------------------------------------
# inputs from the seed
# ---------------------------------------------------------------------------
def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_params(seed: int, m: Dict) -> Params:
    """Initial weights as ``{path: array}``, drawn from ``seed`` in the
    configuration's parameter dtype."""
    dtype = jnp.dtype(m["torch_dtype"])
    d, f, V = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    Dh, L = d // H, m["num_hidden_layers"]
    k_embed, k_layers, _, _ = jax.random.split(jax.random.key(seed), 4)
    k1, _ = jax.random.split(k_embed)

    def fan_in(key, n_in, out_shape):
        return _normal(key, (n_in,) + out_shape, 1.0 / np.sqrt(n_in), dtype)

    def layer(key):
        ks = jax.random.split(jax.random.split(key, 1)[0], 4)
        a1, a2, a3, a4 = jax.random.split(ks[0], 4)
        m1, m2, m3 = jax.random.split(ks[1], 3)
        return {
            "ln1/scale": jnp.ones((d,), dtype),
            "attn/wq": fan_in(a1, d, (H, Dh)),
            "attn/wk": fan_in(a2, d, (KV, Dh)),
            "attn/wv": fan_in(a3, d, (KV, Dh)),
            "attn/wo": _normal(a4, (H, Dh, d), 1.0 / np.sqrt(H * Dh), dtype),
            "ln2/scale": jnp.ones((d,), dtype),
            "mlp/wg": fan_in(m1, d, (f,)),
            "mlp/wu": fan_in(m2, d, (f,)),
            "mlp/wd": fan_in(m3, f, (d,)),
        }

    stacked = jax.vmap(layer)(jax.random.split(k_layers, L))
    out = {"embedding/embed": _normal(k1, (V, d), 0.02, dtype),
           "final_norm/scale": jnp.ones((d,), dtype)}
    out.update({f"layers/sub_0/{k}": v for k, v in stacked.items()})
    return out


def token_batch(seed: int, step: int, n_shards: int, shard: int, rows: int,
                seq: int, vocab: int, structure: float = 0.8
                ) -> Tuple[np.ndarray, np.ndarray]:
    """One worker's rows: t_{i+1} = (31337 t_i + 917) mod V with
    probability ``structure``, else uniform; keyed by (seed, step, shard)."""
    rng = np.random.default_rng((seed * 1_000_003 + step) * n_shards + shard)
    a = 31337 % vocab or 1
    toks = np.empty((rows, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, rows)
    structured = rng.random((rows, seq)) < structure
    noise = rng.integers(0, vocab, (rows, seq))
    for i in range(seq):
        nxt = (a * toks[:, i] + 917) % vocab
        toks[:, i + 1] = np.where(structured[:, i], nxt, noise[:, i])
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _mm(spec, a, b, mode):
    if mode == "fp8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    S, Dh = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, Dh // 2, dtype=np.float32)
                             * 2.0 / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def loss(p: Params, tokens, labels, m: Dict, mode: str = "f32"):
    """Mean next-token cross-entropy over all positions."""
    d, H = m["hidden_size"], m["num_attention_heads"]
    KV, Dh = m["num_key_value_heads"], d // H
    # the epsilon the program runs (``assumed``: it departs from the source)
    eps, theta = m["assumed"]["rms_norm_eps"], m["rope_theta"]
    S = tokens.shape[1]
    kv_of = np.minimum(np.arange(H) // max(H // KV, 1), KV - 1)
    causal = np.tril(np.ones((S, S), bool))
    x = p["embedding/embed"][tokens]
    for i in range(m["num_hidden_layers"]):
        w = {k.split("/", 2)[2]: v[i] for k, v in p.items()
             if k.startswith("layers/")}
        h = _rms(x, w["ln1/scale"], eps)
        q = _rope(_mm("bsd,dhe->bshe", h, w["attn/wq"], mode), theta)
        k = _rope(_mm("bsd,dhe->bshe", h, w["attn/wk"], mode),
                  theta)[:, :, kv_of]
        v = _mm("bsd,dhe->bshe", h, w["attn/wv"], mode)[:, :, kv_of]
        s = _mm("bqhe,bkhe->bhqk", q, k, mode) / np.sqrt(Dh)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        ctx = _mm("bhqk,bkhe->bqhe", a, v, mode)
        x = x + _mm("bshe,hed->bsd", ctx, w["attn/wo"], mode)
        h = _rms(x, w["ln2/scale"], eps)
        g = jax.nn.silu(_mm("bsd,df->bsf", h, w["mlp/wg"], mode))
        u = _mm("bsd,df->bsf", h, w["mlp/wu"], mode)
        x = x + _mm("bsf,fd->bsd", g * u, w["mlp/wd"], mode)
    x = _rms(x, p["final_norm/scale"], eps)
    logits = _mm("bsd,vd->bsv", x, p["embedding/embed"], mode)
    label_logit = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - label_logit)


def _row_grad_fn(m: Dict, mode: str):
    return jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, t, l, m, mode)))


# ---------------------------------------------------------------------------
# the asynchronous parameter server
# ---------------------------------------------------------------------------
def _tree_sum(trees):
    return {k: sum(t[k] for t in trees) for k in trees[0]}


def run(seed: int, m: Dict, t: Dict, steps: int = 3, mode: str = "f32"
        ) -> Dict:
    """Run ``steps`` PS steps of the cell (``m``: model sizes, ``t``:
    traffic) and return per step the mean burst loss, the drained rows
    and the raw updates they carry; the first step's gradient as Adam gets
    it; and the initial and final parameters (float32 numpy, by path)."""
    W, U, Q = t["workers"], t["burst"], t["queue_slots"]
    k_drain = max(1, min(t["drain_k"], Q))
    rows = t["batch_per_worker"]
    n_clusters = max(W // 2, 2)
    b1, b2, eps, lr, clip = 0.9, 0.95, 1e-8, t["lr"], 1.0
    grad_fn = _row_grad_fn(m, "fp8" if mode == "fp8" else "f32")
    p0 = init_params(seed, m)
    params = {k: v.astype(jnp.float32) for k, v in p0.items()}
    mom = {k: jnp.zeros_like(v) for k, v in params.items()}
    vel = {k: jnp.zeros_like(v) for k, v in params.items()}
    rng = np.random.default_rng(seed)
    speed = 1.0 + 0.5 * rng.random(W)
    nxt = np.zeros(W)
    wstep = np.zeros(W, int)
    queue: List[Dict] = []  # slots in departure order
    out = dict(losses=[], applied=[], combined=[])
    for step in range(1, steps + 1):
        burst_losses = []
        for _ in range(U):
            w = int(np.argmin(nxt))
            toks, labs = token_batch(seed, int(wstep[w]), W, w, rows,
                                     t["seq"], m["vocab_size"])
            use = rows // 2 if mode == "half_batch" else rows
            vals, grad = [], None
            for r in range(use):
                v, g = grad_fn(params, toks[r:r + 1], labs[r:r + 1])
                vals.append(v)
                grad = g if grad is None else _tree_sum([grad, g])
            grad = {k: v / use for k, v in grad.items()}
            burst_losses.append(float(np.mean([float(v) for v in vals])))
            wstep[w] += 1
            nxt[w] += speed[w]
            c = w % n_clusters
            slot = next((s for s in queue if s["cluster"] == c), None)
            if slot is None:
                if len(queue) < Q:
                    queue.append(dict(cluster=c, worker=w, replaceable=True,
                                      members=[grad]))
            elif slot["replaceable"] and slot["worker"] == w:
                slot["members"] = [grad]
            else:
                slot.update(worker=w, replaceable=False,
                            members=slot["members"] + [grad])
        drained, queue = queue[:k_drain], queue[k_drain:]
        members = [g for s in drained for g in s["members"]]
        n = len(members)
        g = ({k: v / n for k, v in _tree_sum(members).items()} if n
             else {k: jnp.zeros_like(v) for k, v in params.items()})
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in g.values()))
        scale = jnp.minimum(1.0, clip / (gn + 1e-9))
        g = {k: v * scale for k, v in g.items()}
        if step == 1:
            out["grad1"] = {k: np.asarray(v) for k, v in g.items()}
        mom = {k: b1 * mom[k] + (1 - b1) * g[k] for k in g}
        vel = {k: b2 * vel[k] + (1 - b2) * jnp.square(g[k]) for k in g}
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
        # parameters are kept in the dtype the configuration states
        params = {k: (params[k] - lr * (mom[k] / bc1)
                      / (jnp.sqrt(vel[k] / bc2) + eps)).astype(
                          p0[k].dtype).astype(jnp.float32) for k in params}
        out["losses"].append(float(np.mean(burst_losses)))
        out["applied"].append(len(drained))
        out["combined"].append(n)
    out["p0"] = {k: np.asarray(v, np.float32) for k, v in p0.items()}
    out["p_end"] = {k: np.asarray(v) for k, v in params.items()}
    return out


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------
def leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
             keep=None) -> Tuple[float, str]:
    """Worst leaf's gap of norms, ``|‖got‖ - ‖want‖|`` over the larger of
    ``‖want‖`` and the median leaf's ``‖want‖``; ``keep`` limits the
    leaves compared."""
    norms = {k: float(np.linalg.norm(np.asarray(v, np.float64)))
             for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    worst, where = 0.0, ""
    for k in sorted(want):
        if keep is not None and k not in keep:
            continue
        g = float(np.linalg.norm(np.asarray(got[k], np.float64)))
        gap = abs(g - norms[k]) / max(norms[k], med, 1e-30)
        if gap >= worst:
            worst, where = gap, k
    return worst, where


def moved_leaves(grad1: Dict[str, np.ndarray], floor: float = 1e-3):
    """Leaves whose first gradient is at least ``floor`` of the median
    leaf's: the others move under Adam by round-off alone."""
    norms = {k: float(np.linalg.norm(v)) for k, v in grad1.items()}
    med = float(np.median(list(norms.values())))
    return {k for k, v in norms.items() if v >= floor * med}


def compare(got: Dict, ref: Dict) -> Tuple[Dict[str, float], Dict[str, str]]:
    """The numbers the cell's limits hold: the worst relative loss gap over
    the compared steps, the worst leaf's gap of first-gradient norms and of
    parameter-change norms, and how many drained-row and raw-update counts
    differ; and the leaf that set each worst gap."""
    n = len(ref["losses"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in
                   zip(got["losses"][:n], ref["losses"]))
    grad_gap, grad_leaf = leaf_gap(got["grad1"], ref["grad1"])
    delta_ref = {k: ref["p_end"][k] - ref["p0"][k] for k in ref["p0"]}
    delta_got = {k: got["p_end"][k] - ref["p0"][k] for k in ref["p0"]}
    delta_gap, delta_leaf = leaf_gap(delta_got, delta_ref,
                            keep=moved_leaves(ref["grad1"]))
    want = ref["applied"] + ref["combined"]
    have = list(got["applied"][:n]) + list(got["combined"][:n])
    counts = sum(int(a != b) for a, b in zip(have, want))
    counts += abs(len(want) - len(have))
    return (dict(loss_gap=loss_gap, grad_gap=grad_gap, delta_gap=delta_gap,
                 count_mismatch=float(counts)),
            dict(grad_gap=grad_leaf, delta_gap=delta_leaf))
