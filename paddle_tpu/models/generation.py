"""Autoregressive generation for the Llama family — the TPU-native decode
loop (the reference serves generation through PaddleNLP's
`model.generate`; here it ships in-tree so the framework is servable
standalone).

TPU-first design: generation is ONE compiled program per (batch, prompt
bucket, max_new_tokens) — prefill fills a preallocated KV cache
[layers, b, max_len, kv_heads, head_dim], then a `lax.scan` over decode
steps runs the single-token forward against the cache with a length mask.
Static shapes throughout (the cache is max_len from the start), no host
round-trips inside the loop, early EOS handled by masking rather than
dynamic exit so the program stays trace-stable. GQA attends with grouped
KV via reshape (no repeat materialization). Weights ride as jit operands,
so the same compiled loop serves updated checkpoints without retracing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor

__all__ = ["generate"]


def _quantize_weight_int8(w):
    """Per-output-channel symmetric int8 weight-only quantization for
    decode: HBM reads of the matmul weights halve vs bf16 (decode is
    bandwidth-bound — PERF.md decode accounting). Delegates to the ONE
    shared helper (`quantization.quantize_weight_int8`) so the decode
    pack and Int8Linear cannot diverge; `_mm` dequantizes in-register
    (XLA fuses the convert into the dot's operand read)."""
    from ..quantization import quantize_weight_int8

    return quantize_weight_int8(w)


def _mm(x, w):
    """x @ w where w is a plain array or an int8 weight-only pack."""
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _collect_params(model, int8_weights=False):
    """Pull the Llama weight pytree out of the Layer graph (stacked per
    layer so the decode program scans over layers, O(1) compile in
    depth). Cached on the model keyed by the parameter array identities,
    so repeated generate() calls don't re-copy the weights; any weight
    update (new arrays) invalidates the cache. ``int8_weights`` packs
    the large matmul weights (qkv/o/gate_up/down/lm_head) as
    per-channel int8 (reference analogue: weight-only quantized
    inference kernels); embeddings/norms stay in the model dtype."""
    core = model.model
    sources = tuple(p._data for _, p in model.named_parameters())
    cached = getattr(model, "_generation_params_cache", None)
    if cached is not None and len(cached) == 3 \
            and cached[2] == int8_weights \
            and len(cached[0]) == len(sources) \
            and all(a is b for a, b in zip(cached[0], sources)):
        return cached[1]

    def arr(p):
        return p._data

    per_layer = {
        "ln1": [], "qkv": [], "o": [], "ln2": [], "gate_up": [], "down": [],
    }
    for blk in core.layers:
        per_layer["ln1"].append(arr(blk.input_layernorm.weight))
        per_layer["qkv"].append(arr(blk.self_attn.qkv_proj.weight))
        per_layer["o"].append(arr(blk.self_attn.o_proj.weight))
        per_layer["ln2"].append(arr(blk.post_attention_layernorm.weight))
        per_layer["gate_up"].append(arr(blk.mlp.gate_up_proj.weight))
        per_layer["down"].append(arr(blk.mlp.down_proj.weight))
    params = {k: jnp.stack(v) for k, v in per_layer.items()}
    params["embed"] = arr(core.embed_tokens.weight)
    params["norm"] = arr(core.norm.weight)
    params["lm_head"] = arr(model.lm_head.weight)
    if int8_weights:
        for key in ("qkv", "o", "gate_up", "down", "lm_head"):
            params[key] = _quantize_weight_int8(params[key])
    # the cache keeps the SOURCE arrays alive so identity comparison is sound
    model._generation_params_cache = (sources, params, int8_weights)
    return params


def _rms(x, w, eps):
    # every family's norm: the ``norm`` scope (monitor/scopes.py)
    with jax.named_scope("norm"):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(
            x.dtype) * w


def _rope_at(q, k, pos, theta):
    """RoPE with per-token absolute positions — the SAME helper the
    training forward uses (`llama._rope`), so the two paths cannot drift
    in convention."""
    from .llama import _rope

    return _rope(q, k, theta, q.dtype, pos=pos)


def _attend(q, kc, vc, valid_len, nh, nkv, key_pad=None,
            sliding_window=0):
    """q [b, sq, nh, d] against cached kc/vc [b, L, nkv, d], masked to
    positions < valid_len (+ causal within the query block, + the
    sliding-window band when configured). ``key_pad`` [b] hides each
    row's leading left-pad slots."""
    b, sq, _, d = q.shape
    L = kc.shape[1]
    g = nh // nkv
    qg = q.reshape(b, sq, nkv, g, d)
    logits = jnp.einsum("bskgd,blkd->bskgl", qg.astype(jnp.float32),
                        kc.astype(jnp.float32)) / np.sqrt(d)
    # key position l is visible to query token t (absolute pos
    # valid_len - sq + t) iff l <= that position
    q_pos = valid_len - sq + jnp.arange(sq)  # [sq]
    vis = jnp.arange(L)[None, :] <= q_pos[:, None]  # [sq, L]
    if sliding_window > 0:  # local attention: key within the lookback band
        vis &= jnp.arange(L)[None, :] > q_pos[:, None] - sliding_window
    vis = jnp.broadcast_to(vis[None], (b, sq, L))
    if key_pad is not None:
        vis = vis & (jnp.arange(L)[None, None, :]
                     >= key_pad[:, None, None])
    logits = jnp.where(vis[:, :, None, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bskgl,blkd->bskgd", p, vc.astype(jnp.float32))
    return out.reshape(b, sq, nh, d).astype(q.dtype)


def _block(x, layer_p, cache_k, cache_v, li, pos, valid_len, cfg,
           key_pad=None, kv_int8=False):
    """One decoder layer over a [b, s] slice, reading/writing the cache at
    ``pos``. Returns (x_out, new_cache_k, new_cache_v).

    ``kv_int8`` (static) round-trips the freshly-RoPE'd K/V through the
    shared int8 quant/dequant (`quantization.quantize_kv`) before the
    cache write — the cache still stores the model dtype, but every
    cached value is exactly what the serving engine's int8 block pool
    would reproduce (quantize-on-write there, dequant-on-read here:
    identical fp32 ops either way), so ``generate(kv_int8=True)`` IS
    the token-identity reference for `PT_SERVE_KV_INT8` engines
    (tests/test_serving_kv_int8.py)."""
    nh = cfg.num_attention_heads
    nkv = cfg.num_key_value_heads or nh
    d = cfg.hidden_size // nh
    h = _rms(x, layer_p["ln1"], cfg.rms_norm_eps)
    qkv = _mm(h, layer_p["qkv"])
    q, k, v = jnp.split(qkv, [nh * d, nh * d + nkv * d], axis=-1)
    b, s = x.shape[0], x.shape[1]
    q = q.reshape(b, s, nh, d)
    k = k.reshape(b, s, nkv, d)
    v = v.reshape(b, s, nkv, d)
    q, k = _rope_at(q, k, pos, cfg.rope_theta)
    if kv_int8:
        from ..quantization import dequantize_kv, quantize_kv

        k = dequantize_kv(*quantize_kv(k), k.dtype)
        v = dequantize_kv(*quantize_kv(v), v.dtype)
    ck = cache_k.at[li].set(
        jax.lax.dynamic_update_slice_in_dim(cache_k[li], k,
                                            valid_len - s, 1))
    cv = cache_v.at[li].set(
        jax.lax.dynamic_update_slice_in_dim(cache_v[li], v,
                                            valid_len - s, 1))
    out = _attend(q, ck[li], cv[li], valid_len, nh, nkv,
                  key_pad=key_pad, sliding_window=cfg.sliding_window)
    out = _mm(out.reshape(b, s, nh * d), layer_p["o"])
    x = x + out
    h2 = _rms(x, layer_p["ln2"], cfg.rms_norm_eps)
    gu = _mm(h2, layer_p["gate_up"])
    gate, up = jnp.split(gu, 2, axis=-1)
    x = x + _mm(jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype)
                * up, layer_p["down"])
    return x, ck, cv


def _forward(params, ids, cache_k, cache_v, valid_len, cfg,
             key_pad=None, kv_int8=False):
    """Forward [b, s] token ids at absolute positions
    [valid_len - s, valid_len), attending over the cache. With left
    padding (``key_pad`` [b]), RoPE positions shift so each row's first
    REAL token sits at position 0. Returns (last-position logits,
    cache_k, cache_v)."""
    b, s = ids.shape
    x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    pos = (valid_len - s + jnp.arange(s))[None, :].repeat(b, axis=0)
    if key_pad is not None:
        pos = jnp.maximum(pos - key_pad[:, None], 0)
    n_layers = params["ln1"].shape[0]

    def body(carry, li):
        x, ck, cv = carry
        layer_p = {k: jax.tree_util.tree_map(lambda a: a[li], params[k])
                   for k in
                   ("ln1", "qkv", "o", "ln2", "gate_up", "down")}
        x, ck, cv = _block(x, layer_p, ck, cv, li, pos, valid_len, cfg,
                           key_pad=key_pad, kv_int8=kv_int8)
        return (x, ck, cv), None

    (x, cache_k, cache_v), _ = jax.lax.scan(
        body, (x, cache_k, cache_v), jnp.arange(n_layers))
    x = _rms(x, params["norm"], cfg.rms_norm_eps)
    logits = _mm(x[:, -1], params["lm_head"])
    return logits.astype(jnp.float32), cache_k, cache_v


def _sample(logits, key, do_sample, temperature, top_k, top_p,
            use_top_p):
    """do_sample/top_k/use_top_p are static (program structure);
    temperature and the top_p VALUE ride as traced scalars, so changing
    either between requests never retraces — only toggling top-p
    filtering on/off does (a legitimate structure change that spares the
    default path a full-vocab sort per token)."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if use_top_p:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)  # first index past p
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1)


class _GenCfg:
    """Value-hashable static view of the LlamaConfig fields the decode
    trace depends on — in-place config mutation or a fresh but identical
    config can never serve a stale compiled program (LlamaConfig hashes
    by identity)."""

    __slots__ = ("num_attention_heads", "num_key_value_heads",
                 "hidden_size", "rope_theta", "rms_norm_eps", "dtype",
                 "sliding_window")

    def __init__(self, cfg):
        self.num_attention_heads = cfg.num_attention_heads
        self.num_key_value_heads = cfg.num_key_value_heads \
            or cfg.num_attention_heads
        self.hidden_size = cfg.hidden_size
        self.rope_theta = float(cfg.rope_theta)
        self.rms_norm_eps = float(cfg.rms_norm_eps)
        self.dtype = str(cfg.dtype)
        self.sliding_window = int(getattr(cfg, "sliding_window", 0) or 0)

    def _key(self):
        return tuple(getattr(self, f) for f in self.__slots__)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, _GenCfg) and self._key() == other._key()


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "do_sample", "top_k",
                     "use_top_p", "eos_token_id", "kv_int8"))
def _generate_jit(params, ids, key, temperature, top_p, key_pad, *, cfg,
                  max_new_tokens, do_sample, top_k, use_top_p,
                  eos_token_id, kv_int8=False):
    b, prompt_len = ids.shape
    nh = cfg.num_attention_heads
    nkv = cfg.num_key_value_heads or nh
    d = cfg.hidden_size // nh
    max_len = prompt_len + max_new_tokens
    dt = jnp.dtype(cfg.dtype)
    cache_k = jnp.zeros((params["ln1"].shape[0], b, max_len, nkv, d), dt)
    cache_v = jnp.zeros_like(cache_k)

    # prefill: the whole prompt in one batched pass
    logits, cache_k, cache_v = _forward(params, ids, cache_k, cache_v,
                                        jnp.asarray(prompt_len), cfg,
                                        key_pad=key_pad, kv_int8=kv_int8)
    key, sub = jax.random.split(key)
    next_tok = _sample(logits, sub, do_sample, temperature,
                       top_k, top_p, use_top_p)
    eos = -1 if eos_token_id is None else int(eos_token_id)
    finished = next_tok == eos

    def step(carry, i):
        tok, ck, cv, fin, key = carry
        valid = prompt_len + 1 + i
        logits, ck, cv = _forward(params, tok[:, None], ck, cv, valid,
                                  cfg, key_pad=key_pad, kv_int8=kv_int8)
        key, sub = jax.random.split(key)
        nxt = _sample(logits, sub, do_sample, temperature,
                      top_k, top_p, use_top_p)
        # after EOS keep emitting EOS (masking, not dynamic exit)
        nxt = jnp.where(fin, eos, nxt)
        fin = fin | (nxt == eos)
        return (nxt, ck, cv, fin, key), tok

    (last, *_rest), toks = jax.lax.scan(
        step, (next_tok, cache_k, cache_v, finished, key),
        jnp.arange(max_new_tokens - 1))
    # toks holds tokens emitted BEFORE each step; append the final one
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)
    return out


def generate(model, input_ids, max_new_tokens=32, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             seed=0, attention_mask=None, int8_weights=None,
             kv_int8=None):
    """Generate ``max_new_tokens`` continuations of ``input_ids``
    ([b, prompt_len] int tensor) with the compiled KV-cache decode loop.
    Returns the generated tokens [b, max_new_tokens] (prompt excluded).

    Unequal-length prompts batch via LEFT padding + ``attention_mask``
    ([b, prompt_len] 1/0, zeros on the left): pad slots are hidden from
    attention and RoPE positions start at each row's first real token.
    Without a mask, prompts must be all-real tokens.

    ``kv_int8`` (default: ``PT_SERVE_KV_INT8``) round-trips cached K/V
    through the shared symmetric int8 quant/dequant — the reference the
    int8-pool serving engine is proven token-identical against (see
    `_block`)."""
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if hasattr(model, "serving_family"):
        from ..framework.errors import UnimplementedError

        raise UnimplementedError(
            f"generate() keeps a contiguous [layers, b, len, kv_heads, "
            f"head_dim] K/V cache and scans one stacked layer body; "
            f"{type(model).__name__} brings its own cache and step "
            f"programs (model.serving_family: the "
            f"{model.serving_family_name!r} family): serve it through "
            f"paddle_tpu.serving.ServingEngine")
    if getattr(model.config, "moe_num_experts", 0) > 1:
        from ..framework.errors import UnimplementedError

        raise UnimplementedError(
            "generate() does not decode MoE Llama configs yet (the expert "
            "dispatch needs its own cached single-token path); dense "
            "configs are supported")
    import os

    if int8_weights is None:
        int8_weights = os.environ.get("PT_DECODE_INT8") == "1"
    if kv_int8 is None:
        kv_int8 = os.environ.get("PT_SERVE_KV_INT8") == "1"
    params = _collect_params(model, int8_weights=int8_weights)
    ids = input_ids._data if isinstance(input_ids, Tensor) \
        else jnp.asarray(np.asarray(input_ids))
    # every operand must sit on one device set or jit rejects the mix.
    # Two asymmetric cases exist in the wild: (a) a live mesh with
    # weights created BEFORE it existed (model built pre-fleet.init —
    # the param-place hook only covers params created after install);
    # (b) NO live env but mesh-placed weights (a TP-annotated model
    # whose env was reset/re-made — the arrays keep their NamedShardings).
    # Normalize to the mesh the params carry, else the live env's mesh.
    from jax.sharding import NamedSharding

    from ..distributed import env as env_mod

    e = env_mod.get_env()
    param_mesh = None
    for a in jax.tree_util.tree_leaves(params):
        s = getattr(a, "sharding", None)
        if isinstance(s, NamedSharding) and len(s.device_set) > 1:
            param_mesh = s.mesh
            break
    if param_mesh is None and e is not None:
        param_mesh = e.mesh
    if param_mesh is not None:
        ids = env_mod.put_replicated(ids, param_mesh)
        params = jax.tree_util.tree_map(
            lambda a: env_mod.ensure_on_mesh(a, param_mesh), params)
    if top_k:
        top_k = min(int(top_k), model.config.vocab_size)
    key_pad = None
    if attention_mask is not None:
        m = attention_mask._data if isinstance(attention_mask, Tensor) \
            else jnp.asarray(np.asarray(attention_mask))
        if m.shape != ids.shape:
            raise ValueError(
                f"attention_mask shape {tuple(m.shape)} must equal "
                f"input_ids shape {tuple(ids.shape)}")
        # validate host-side in one pass (tiny array; avoids device
        # round-trips): each row must be 0^k 1^(n-k) — LEFT padding
        mh = np.asarray(m).astype(bool)
        npad_h = (~mh).sum(axis=1)
        expect = np.arange(mh.shape[1])[None, :] >= npad_h[:, None]
        if not np.array_equal(mh, expect):
            raise ValueError(
                "attention_mask must be LEFT-padded (each row all zeros "
                "then all ones); interior zeros / right padding are not "
                "expressible in the cache layout")
        if npad_h.any():  # all-ones mask == no mask: share the
            key_pad = jnp.asarray(npad_h, jnp.int32)  # maskless program
            if param_mesh is not None:
                key_pad = env_mod.put_replicated(key_pad, param_mesh)
    out = _generate_jit(
        params, ids.astype(jnp.int32), jax.random.key(seed),
        jnp.float32(temperature), jnp.float32(top_p), key_pad,
        cfg=_GenCfg(model.config), max_new_tokens=int(max_new_tokens),
        do_sample=bool(do_sample), top_k=int(top_k),
        use_top_p=float(top_p) < 1.0,
        eos_token_id=eos_token_id, kv_int8=bool(kv_int8))
    return Tensor(out)
