"""Llama-family causal LM, TPU-first.

This is the flagship benchmark model (BASELINE.md config 4: Llama-2-7B,
hybrid TP×DP, ≥45% MFU target). The reference distributes Llama through
PaddleNLP on top of the fleet meta-parallel layers
(`fleet/layers/mpu/mp_layers.py`); this in-tree implementation plays that
role, built on the same paddle-shaped pieces:

- TP: fused-QKV `ColumnParallelLinear` → `RowParallelLinear` conjugate pairs
  (one sharding annotation each; XLA emits Megatron's f/g collectives).
- SP: optional sequence-sharded residual stream between the pairs
  (`sequence_parallel` flag — reference `sequence_parallel_utils.py`).
- Attention: `scaled_dot_product_attention` routed through the
  "flash_attention" op so the Pallas splash kernel takes over on TPU.
- GQA: num_key_value_heads < num_attention_heads repeats KV.
- PP: `LlamaForCausalLMPipe` expresses the decoder stack as LayerDescs for
  the GSPMD shifted pipeline (`pp_layers.py`).

Everything is bfloat16-friendly: params can be created in bf16 (`dtype`
config) and the loss path upcasts to f32 where it matters (softmax, CE).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from .. import tensor as T
from ..distributed import shard
from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear, LayerDesc, ParallelCrossEntropy, PipelineLayer,
    masked_token_mean,
    RowParallelLinear, VocabParallelEmbedding,
)
from ..framework.core import Tensor
from ..nn import functional as F
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.dispatch import apply


class LlamaConfig:
    def __init__(
        self,
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=11008,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=None,
        max_position_embeddings=4096,
        rms_norm_eps=1e-5,
        rope_theta=10000.0,
        tie_word_embeddings=False,
        sequence_parallel=False,
        context_parallel=False,
        context_parallel_mode="ring",
        sliding_window=0,
        use_parallel_cross_entropy=True,
        ce_chunk_size=0,
        recompute=False,
        dtype="float32",
        moe_num_experts=0,
        moe_top_k=2,
        moe_expert_axis="dp",
        moe_aux_loss_coeff=0.01,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel
        if context_parallel_mode not in ("ring", "ulysses"):
            raise ValueError(
                "context_parallel_mode must be 'ring' (KV rotation, "
                "extreme lengths) or 'ulysses' (head/seq all-to-all, "
                f"plentiful heads); got {context_parallel_mode!r}")
        self.context_parallel_mode = context_parallel_mode
        # Mistral-style local attention (0 = full causal); training and
        # the compiled KV-cache decode honor the same band
        if not isinstance(sliding_window, int) or sliding_window < 0:
            raise ValueError(
                "sliding_window must be a non-negative int (0 = full "
                f"causal), got {sliding_window!r}")
        if sliding_window and context_parallel:
            raise ValueError(
                "sliding_window with context_parallel is unsupported: the "
                "ring/ulysses paths assume full causal attention")
        self.sliding_window = sliding_window
        self.use_parallel_cross_entropy = use_parallel_cross_entropy
        # >0: the training loss uses F.chunked_softmax_cross_entropy —
        # the [N, V] fp32 logits never materialize (HBM win at V=32000);
        # single-chip / non-parallel-CE path only
        if ce_chunk_size > 0 and use_parallel_cross_entropy:
            raise ValueError(
                "ce_chunk_size requires use_parallel_cross_entropy=False: "
                "the chunked loss consumes the unsharded lm_head weight; "
                "under TP use ParallelCrossEntropy instead (it already "
                "avoids gathering vocab-sharded logits)")
        self.ce_chunk_size = ce_chunk_size
        self.recompute = recompute
        self.dtype = dtype
        self.moe_num_experts = moe_num_experts
        self.moe_top_k = moe_top_k
        self.moe_expert_axis = moe_expert_axis
        self.moe_aux_loss_coeff = moe_aux_loss_coeff

    @classmethod
    def llama2_7b(cls, **kw):
        """Llama-2-7B's published widths; ``kw`` overrides (a depth cut
        to fit one chip is ``num_hidden_layers=N`` — chip_smoke.py)."""
        base = dict(hidden_size=4096, intermediate_size=11008,
                    num_hidden_layers=32, num_attention_heads=32)
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny(cls, **kw):
        """Test/dry-run config."""
        kw.setdefault("vocab_size", 128)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 4)
        kw.setdefault("num_attention_heads", 4)
        kw.setdefault("max_position_embeddings", 128)
        return cls(**kw)


def _rope(q_arr, k_arr, theta, dtype, pos=None):
    """Rotary position embedding applied to [b, s, h, d] q/k arrays
    (pure-jnp; runs inside the recorded op so its vjp is automatic).
    ``pos`` ([s] or [b, s] absolute positions) defaults to arange(s);
    the cached decode path passes explicit positions."""
    b, s, h, d = q_arr.shape
    if pos is None:
        pos = jnp.arange(s, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos.astype(jnp.float32)[..., None] * inv  # [.., s, d/2]
    if freqs.ndim == 2:  # [s, d/2] -> broadcast over batch
        cos = jnp.cos(freqs)[None, :, None, :]
        sin = jnp.sin(freqs)[None, :, None, :]
    else:  # [b, s, d/2]
        cos = jnp.cos(freqs)[:, :, None, :]
        sin = jnp.sin(freqs)[:, :, None, :]

    def rot(x):
        # half-split rotate_half (HF-Llama) pairing: (x_i, x_{i+d/2})
        # rotated by freq_i. (Beware Paddle's flag naming: its
        # use_neox_rotary_style=True selects the *interleaved* pairing —
        # see docs/MIGRATION.md pitfall 5.)
        # TPU-deliberate: the interleaved (x_{2i}, x_{2i+1})
        # pairing needs stride-2 lane shuffles that XLA materializes as
        # relayout copies (~4% of the headline train step, profiled);
        # contiguous halves are cheap lane slices. Both are valid RoPE
        # (the relative-position identity holds per pair); train and
        # decode share this helper so the convention cannot drift.
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        xr1 = x1 * cos - x2 * sin
        xr2 = x2 * cos + x1 * sin
        out = jnp.concatenate([xr1, xr2], axis=-1)
        return out.astype(dtype)

    if k_arr is None:
        return rot(q_arr.astype(jnp.float32)), None
    return rot(q_arr.astype(jnp.float32)), rot(k_arr.astype(jnp.float32))


def apply_rotary_pos_emb(q, k, theta=10000.0, position_ids=None):
    """Paddle-shaped rope entry (parity: fused_rotary_position_embedding in
    `paddle/incubate/nn/functional`). ``position_ids`` ([s] or [b, s])
    overrides the default arange positions (cached-decode offsets)."""
    dtype = q._data.dtype if isinstance(q, Tensor) else q.dtype
    pos = position_ids
    if isinstance(pos, Tensor):
        pos = pos._data
    return apply("rope",
                 lambda qa, ka: _rope(qa, ka, theta, dtype, pos=pos),
                 (q, k), n_outputs=2)


def apply_rotary_pos_emb_single(x, theta=10000.0, position_ids=None):
    """Rotate one array (the fused-rope v input) without paying a second
    rotation for a discarded slot."""
    dtype = x._data.dtype if isinstance(x, Tensor) else x.dtype
    pos = position_ids
    if isinstance(pos, Tensor):
        pos = pos._data
    return apply("rope_single",
                 lambda xa: _rope(xa, None, theta, dtype, pos=pos)[0],
                 (x,))


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        nh = config.num_attention_heads
        nkv = config.num_key_value_heads
        self.head_dim = h // nh
        self.num_heads = nh
        self.num_kv_heads = nkv
        qkv_out = (nh + 2 * nkv) * self.head_dim
        # fused QKV, column-parallel over heads
        self.qkv_proj = ColumnParallelLinear(h, qkv_out, has_bias=False,
                                             gather_output=False)
        self.o_proj = RowParallelLinear(nh * self.head_dim, h, has_bias=False,
                                        input_is_parallel=True)

    def forward(self, x):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        q_sz = self.num_heads * self.head_dim
        kv_sz = self.num_kv_heads * self.head_dim
        q, k, v = T.split(qkv, [q_sz, kv_sz, kv_sz], axis=-1)
        q = q.reshape([b, s, self.num_heads, self.head_dim])
        k = k.reshape([b, s, self.num_kv_heads, self.head_dim])
        v = v.reshape([b, s, self.num_kv_heads, self.head_dim])
        q, k = apply_rotary_pos_emb(q, k, cfg.rope_theta)
        if self.num_kv_heads != self.num_heads:
            # GQA: scaled_dot_product_attention handles grouped KV
            # natively (Pallas shared-KV index maps / composite repeat),
            # so the repeat is only materialized when (a) the ring
            # context-parallel path runs (it requires equal head counts)
            # or (b) mp sharding couldn't split the unrepeated KV heads
            from ..distributed import env as env_mod

            e = env_mod.get_env()
            mp = e.degree("mp") if e is not None else 1
            if cfg.context_parallel or (mp > 1 and self.num_kv_heads % mp):
                rep = self.num_heads // self.num_kv_heads
                k = T.repeat_interleave(k, rep, axis=2)
                v = T.repeat_interleave(v, rep, axis=2)
        if not cfg.context_parallel:
            # heads stay mp-sharded through attention (dim 2); the batch
            # dim keeps its dp split — a constraint that names only one
            # axis forces XLA to drop the other (a full remat copy per
            # layer now that traced constraints are honored, see
            # distributed/shard.py). Under context parallelism the
            # sequence dim is sep-sharded and the ring/ulysses paths own
            # their layouts — constraining seq to None here would
            # all-gather the full sequence CP exists to avoid
            q = shard.sharding_constraint(q, "dp", None, "mp", None)
            k = shard.sharding_constraint(k, "dp", None, "mp", None)
            v = shard.sharding_constraint(v, "dp", None, "mp", None)
        if cfg.context_parallel:
            # exact attention with the sequence sharded across chips
            # (long-context path): KV-rotating ring by default, or
            # Ulysses head/seq all-to-all when configured
            if cfg.context_parallel_mode == "ulysses":
                out = F.ulysses_attention(q, k, v, axis="sep",
                                          causal=True)
            else:
                out = F.ring_flash_attention(q, k, v, axis="sep",
                                             causal=True)
        elif cfg.sliding_window > 0:
            out = F.sliding_window_attention(q, k, v, cfg.sliding_window)
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        h, ffn = config.hidden_size, config.intermediate_size
        # fused gate+up, column-parallel
        self.gate_up_proj = ColumnParallelLinear(h, 2 * ffn, has_bias=False,
                                                 gather_output=False)
        self.down_proj = RowParallelLinear(ffn, h, has_bias=False,
                                           input_is_parallel=True)
        self._ffn = ffn

    def forward(self, x):
        gate_up = self.gate_up_proj(x)
        gate, up = T.split(gate_up, 2, axis=-1)
        return self.down_proj(F.silu(gate) * up)


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        if config.moe_num_experts > 1:
            from ..incubate.distributed.models.moe import MoELayer

            self.mlp = MoELayer(
                config.hidden_size, config.intermediate_size,
                num_experts=config.moe_num_experts,
                top_k=config.moe_top_k, activation="silu",
                expert_axis=config.moe_expert_axis)
        else:
            self.mlp = LlamaMLP(config)

    def forward(self, x):
        sp = self.config.sequence_parallel
        if sp:  # residual stream sequence-sharded over 'mp' (SP), batch
            # still dp-split (hybrid: both axes in one constraint)
            x = shard.sharding_constraint(x, "dp", "mp", None)
        h = x + self.self_attn(self.input_layernorm(x))
        if sp:
            h = shard.sharding_constraint(h, "dp", "mp", None)
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            blk = LlamaDecoderLayer(config)
            self.add_sublayer(f"layers.{i}", blk)
            self.layers.append(blk)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        x = x.astype(self.config.dtype)
        x = shard.sharding_constraint(x, "dp", None, None)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = self.model = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=not config.use_parallel_cross_entropy)
        self.loss_fn = (ParallelCrossEntropy()
                        if config.use_parallel_cross_entropy else None)

    def forward(self, input_ids, labels=None):
        hidden = self.model(input_ids)
        if (labels is not None and self.loss_fn is None
                and self.config.ce_chunk_size > 0):
            # chunked CE: lm_head matmul + softmax + gather fused per
            # vocab chunk — the full fp32 logits never materialize
            per_tok = F.chunked_softmax_cross_entropy(
                hidden, self.lm_head.weight, labels,
                self.config.ce_chunk_size)
            loss = masked_token_mean(per_tok, labels, -100)
            return self._add_moe_aux(loss)
        logits = self.lm_head(hidden)
        if labels is None:
            return logits
        if self.loss_fn is not None:
            loss = self.loss_fn(logits.astype("float32"), labels)
            ignore = self.loss_fn.ignore_index
        else:
            loss = F.cross_entropy(logits.astype("float32"),
                                   labels.unsqueeze(-1), reduction="none")
            ignore = -100
        # divide by the non-ignored token count, not total tokens
        loss = masked_token_mean(loss, labels, ignore)
        return self._add_moe_aux(loss)

    def _add_moe_aux(self, loss):
        if self.config.moe_num_experts > 1:
            # GShard load-balancing aux loss, consumed in the same trace it
            # was produced in (the MoE layers stash it during forward)
            aux = None
            for blk in self.model.layers:
                a = getattr(blk.mlp, "aux_loss", None)
                if a is not None:
                    aux = a if aux is None else aux + a
                    blk.mlp.aux_loss = None
            if aux is not None:
                loss = loss + self.config.moe_aux_loss_coeff * aux
        return loss

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=0, attention_mask=None, kv_int8=None):
        """Compiled KV-cache autoregressive decoding (see
        models/generation.py). Returns [b, max_new_tokens] new tokens."""
        from .generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         do_sample=do_sample, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         eos_token_id=eos_token_id, seed=seed,
                         attention_mask=attention_mask, kv_int8=kv_int8)

    def flops_per_token(self, seq_len):
        """Approximate training FLOPs/token (fwd+bwd) for MFU accounting."""
        cfg = self.config
        n_params = (
            cfg.vocab_size * cfg.hidden_size * 2
            + cfg.num_hidden_layers * (
                cfg.hidden_size * (cfg.num_attention_heads
                                   + 2 * cfg.num_key_value_heads)
                * (cfg.hidden_size // cfg.num_attention_heads)
                + cfg.hidden_size * cfg.hidden_size
                + 3 * cfg.hidden_size * cfg.intermediate_size
            )
        )
        attn = (cfg.num_hidden_layers * 2 * cfg.hidden_size * seq_len)
        return 6 * (n_params + attn)


# ---- pipeline variant ----

class _EmbeddingStage(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        x = x.astype(self.config.dtype)
        return shard.sharding_constraint(x, "dp", None, None)


class _HeadStage(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False,
            gather_output=not config.use_parallel_cross_entropy)

    def forward(self, x):
        return self.lm_head(self.norm(x))


class LlamaForCausalLMPipe(PipelineLayer):
    """Pipeline-parallel Llama: decoder blocks become the stage-stacked
    repeated run (parity: PaddleNLP's LlamaForCausalLMPipe over
    `PipelineLayer`).

    Known limitation: with moe_num_experts>0 the GShard aux loss is not
    surfaced out of the pipelined block scan yet, so load-balancing is not
    optimized under PP (it is under the non-pipe model)."""

    def __init__(self, config: LlamaConfig, **kwargs):
        self.config = config
        ce = ParallelCrossEntropy() if config.use_parallel_cross_entropy else None

        def loss_fn(logits, labels):
            if ce is not None:
                per_tok = ce(logits.astype("float32"), labels)
                return masked_token_mean(per_tok, labels, ce.ignore_index)
            per_tok = F.cross_entropy(logits.astype("float32"),
                                      labels.unsqueeze(-1),
                                      reduction="none")
            return masked_token_mean(per_tok, labels, -100)

        descs = (
            [LayerDesc(_EmbeddingStage, config)]
            + [LayerDesc(LlamaDecoderLayer, config)
               for _ in range(config.num_hidden_layers)]
            + [LayerDesc(_HeadStage, config)]
        )
        super().__init__(
            layers=descs, loss_fn=loss_fn,
            recompute_interval=1 if config.recompute else 0, **kwargs)
