"""Plain reference for the training cell: the first steps of the job
computed in straightforward ``jax.numpy`` at float32, with every matmul
at ``highest`` precision, nothing of the program imported.

Model (the configuration's ``model``, Llama-style as Yi publishes it):
token embedding; per layer ``h += Wo . attn(rope(Wq x), rope(Wk x), Wv x)``
with ``x = rmsnorm(h)``, causal grouped-query attention (query head
``i`` reads key/value head ``i // (heads / kv_heads)``) and rotary
embeddings over the two halves of each head; then ``h += W2 (silu(W3 x)
* W1 x)`` with ``x = rmsnorm(h)``; a final rmsnorm and the output head.
Loss: mean over every position but each sequence's last of the
next-token cross entropy, plus ``z_loss`` times the mean squared
log-partition.  Optimizer: AdamW as the configuration states it (global
gradient-norm clipping, bias-corrected moments, decoupled weight decay
on matrices, linear warm-up then cosine learning rate).

The weights come from ``bench/gen/lm_weights.py`` with the run's seed,
rounded to the type the configuration serves them in; the tokens come
from ``bench/gen/lm_corpus.py``, each step's rows by the input path's
ordering contract.  Gradients accumulate one sequence at a time,
attention runs in blocks of queries, and Adam's moments wait on the
host between steps, each leaf updated on its own, so the reference fits
beside nothing else on one chip.

``matmul_dtype="float8"`` is the control, in the precision below the
configuration's bfloat16: every matmul operand in fp8 e4m3 and its
gradient in fp8 e5m2, each with a per-tensor scale, everything else as
above.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512


def _names(model: dict) -> list[tuple[str, tuple[int, ...], str]]:
    d, ff = model["hidden_size"], model["intermediate_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd, v = model["head_dim"], model["vocab_size"]
    out = [("embed.tok", (v, d), "param"), ("embed.head", (d, v), "param"),
           ("final_norm.scale", (d,), "norm")]
    for layer in range(model["num_hidden_layers"]):
        p = f"blocks.{layer}."
        out += [(p + "ln1.scale", (d,), "norm"), (p + "ln2.scale", (d,), "norm"),
                (p + "attn.wq", (d, h, hd), "param"),
                (p + "attn.wk", (d, kv, hd), "param"),
                (p + "attn.wv", (d, kv, hd), "param"),
                (p + "attn.wo", (h, hd, d), "param"),
                (p + "mlp.w1", (d, ff), "param"), (p + "mlp.w3", (d, ff), "param"),
                (p + "mlp.w2", (ff, d), "param")]
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """float32 weights holding the served values (matrices rounded to
    the configuration's parameter type, norm scales float32)."""
    from bench.gen import lm_weights
    served = jnp.dtype(cfg["precision"]["params"])
    names = _names(cfg["model"])

    def build(key):
        return {n: lm_weights.leaf(key, n, shape,
                                   jnp.float32 if kind == "norm" else served)
                .astype(jnp.float32) for n, shape, kind in names}
    return jax.jit(build)(lm_weights.base_key(seed))


def _mm(spec: str, a, b, matmul_dtype):
    if matmul_dtype != "float32":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _scaled(x, dtype):
    """x through ``dtype`` with a per-tensor scale that puts its largest
    magnitude on the type's largest finite value, back to float32."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, top / amax, 1.0)
    return (x * s).astype(dtype).astype(jnp.float32) / s


def _fp8_fwd(x):
    return _scaled(x, "float8_e4m3fn"), None


def _fp8_bwd(_, g):
    return (_scaled(g, "float8_e5m2"),)


@jax.custom_vjp
def _fp8(x):
    """The usual fp8 training recipe around a matmul operand: values in
    e4m3 on the way forward, their gradients in e5m2 on the way back,
    each with a per-tensor scale."""
    return _scaled(x, "float8_e4m3fn")


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, matmul_dtype):
    """Causal GQA, one block of queries at a time."""
    b, s, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    nb = max(s // Q_BLOCK, 1)
    qb = q.reshape(b, nb, s // nb, h, hd).swapaxes(0, 1)
    keys = jnp.arange(s)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = _mm("bqhd,bkhd->bhqk", qi, k, matmul_dtype) * hd ** -0.5
        rows = i * (s // nb) + jnp.arange(s // nb)
        sc = jnp.where(rows[:, None] >= keys[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, v, matmul_dtype)

    out = jax.lax.map(block, (qb, jnp.arange(nb)))
    return out.swapaxes(0, 1).reshape(b, s, h, hd)


def loss_sums(params: dict, tokens, cfg: dict, matmul_dtype="float32"):
    """(sum of next-token NLL, sum of squared log-partitions, count)
    over ``tokens`` (b, s)."""
    m = cfg["model"]
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h = params["embed.tok"][tokens]
    for layer in range(m["num_hidden_layers"]):
        p = {k.split(".", 2)[2]: v for k, v in params.items()
             if k.startswith(f"blocks.{layer}.")}
        x = _rmsnorm(h, p["ln1.scale"], eps)
        q = _rope(_mm("bsd,dhk->bshk", x, p["attn.wq"], matmul_dtype), theta)
        k = _rope(_mm("bsd,dhk->bshk", x, p["attn.wk"], matmul_dtype), theta)
        v = _mm("bsd,dhk->bshk", x, p["attn.wv"], matmul_dtype)
        o = _attention(q, k, v, matmul_dtype)
        h = h + _mm("bshk,hkd->bsd", o, p["attn.wo"], matmul_dtype)
        x = _rmsnorm(h, p["ln2.scale"], eps)
        a = jax.nn.silu(_mm("bsd,df->bsf", x, p["mlp.w3"], matmul_dtype)) \
            * _mm("bsd,df->bsf", x, p["mlp.w1"], matmul_dtype)
        h = h + _mm("bsf,fd->bsd", a, p["mlp.w2"], matmul_dtype)
    h = _rmsnorm(h, params["final_norm.scale"], eps)
    logits = _mm("bsd,dv->bsv", h, params["embed.head"], matmul_dtype)
    lse = jax.nn.logsumexp(logits, axis=-1)
    labels = jnp.roll(tokens, -1, axis=1)
    tgt = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    return (jnp.sum((lse - tgt) * mask), jnp.sum(lse * lse * mask),
            jnp.sum(mask))


def lr_at(opt: dict, step: int) -> float:
    w, total = opt["warmup_steps"], opt["total_steps"]
    if step < w:
        return opt["lr"] * step / max(w, 1)
    prog = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    frac = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * frac


def first_steps(cfg: dict, seed: int, batches: list[np.ndarray], *,
                matmul_dtype: str = "float32") -> dict:
    """Run the job's first ``len(batches)`` steps on the given token
    batches ((b, s) int32 each).  Returns each step's loss, each leaf's
    norm of the first step's gradient, and each leaf's norm of the
    parameters' change after the last step."""
    opt, z = cfg["train"]["optimizer"], cfg["train"]["z_loss"]
    b1, b2 = opt["betas"]
    params = init_params(cfg, seed)
    # Adam's moments wait on the host between steps: the chip holds the
    # parameters, the summed gradient and one sequence's work at a time
    m: dict = {k: None for k in params}
    v2: dict = {k: None for k in params}

    def seq_loss(p, toks, count):
        nll, zs, _ = loss_sums(p, toks, cfg, matmul_dtype)
        return (nll + z * zs) / count

    vg = jax.value_and_grad(seq_loss)

    @partial(jax.jit, donate_argnums=(1,))
    def accumulate(p, g, toks, count):
        loss, gi = vg(p, toks, count)
        return loss, jax.tree.map(jnp.add, g, gi)

    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in t.items()})

    @partial(jax.jit, donate_argnums=(0, 3))
    def leaf_update(p, m, v, g, scale, lr, t, wd):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + opt["eps"]) + wd * p), m, v

    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for step, toks in enumerate(batches, start=1):
            count = float(toks.shape[0] * (toks.shape[1] - 1))
            total, g = 0.0, zeros(params)
            for row in toks:
                loss, g = accumulate(params, g, jnp.asarray(row[None]),
                                     count)
                total += float(loss)
            losses.append(total)
            g_norms = {k: float(x) for k, x in norms(g).items()}
            if grad_norms is None:
                grad_norms = g_norms
            gnorm = math.sqrt(sum(x * x for x in g_norms.values()))
            scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
            last = step == len(batches)
            for k in params:
                mk = jnp.zeros_like(params[k]) if m[k] is None else m[k]
                vk = jnp.zeros_like(params[k]) if v2[k] is None else v2[k]
                wd = opt["weight_decay"] if params[k].ndim >= 2 else 0.0
                params[k], mk, vk = leaf_update(
                    params[k], mk, vk, g.pop(k), scale, lr_at(opt, step),
                    float(step), wd)
                m[k], v2[k] = (None, None) if last else \
                    (np.asarray(mk), np.asarray(vk))
                del mk, vk
            del g
        p0 = init_params(cfg, seed)
        change = {k: float(x) for k, x in norms(
            {k: params[k] - p0[k] for k in params}).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def gaps(prog: dict, ref: dict) -> dict:
    """The numbers compared: the gap of the first step's loss, and for
    the first gradient and the parameters' change the largest gap
    between the program's leaf norm and the reference's, over the
    larger of that leaf's and the median leaf's reference norm.  Leaves
    whose reference gradient is under a thousandth of the median leaf's
    (nought to rounding) are left out of both.  The later steps' losses
    are not compared: the first Adam step moves every weight by the
    learning rate at once, the loss spikes, and from there a rounding
    difference grows from step to step (PERF.md gives the readings)."""
    g_med = float(np.median(list(ref["grad_norms"].values())))
    live = [k for k, g in ref["grad_norms"].items() if g >= 1e-3 * g_med]

    def rel(key: str) -> float:
        med = float(np.median([ref[key][k] for k in live]))
        return max(abs(prog[key][k] - ref[key][k]) / max(ref[key][k], med)
                   for k in live)

    return {"first_loss_gap": abs(prog["losses"][0] - ref["losses"][0]),
            "grad_norm_gap": rel("grad_norms"),
            "update_norm_gap": rel("change_norms")}


# limits of the numbers compared, each set between its two readings
# (PERF.md gives them): the largest that sound runs of the program gave
# over 12 seeds, and the least that the fp8 control gave (each of the
# three), half a batch left out (grad_norm_gap) or a step that leaves its
# state unchanged (update_norm_gap, which then reads 1).
LIMITS = {"first_loss_gap": 0.007, "grad_norm_gap": 0.006,
          "update_norm_gap": 0.03}


def check(prog: dict, ref: dict) -> dict:
    return {k: {"value": v, "limit": LIMITS[k]}
            for k, v in gaps(prog, ref).items() if k in LIMITS}
