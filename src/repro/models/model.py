"""Model builder: composes layers into scanned segments for every family.

A model is decomposed into *segments*: maximal runs of a repeating layer
pattern. Uniform models (llama-style) are one segment with a period-1
pattern scanned ``num_layers`` times; gemma3's 5 local : 1 global becomes a
period-6 pattern; jamba's (7 mamba + 1 attn) x (dense|moe alternation)
becomes a period-8 pattern; deepseek-v2's leading dense layer is its own
single-layer segment. Scanning keeps HLO size (and hence compile time for
512-device dry-runs) independent of depth, exactly like MaxText.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import mamba as mamba_lib
from repro.models import moe as moe_lib
from repro.models.spec import ParamSpec, abstract_params, init_params, stack_specs
from repro.sharding import Rules, constrain, tree_shardings

Params = Any


# ---------------------------------------------------------------------------
# layer plans & segmentation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    mixer: str            # "attn" | "mamba"
    mlp: str              # "dense" | "moe"
    window: int           # 0 = full attention
    d_ff: int
    cross_attn: bool = False


def layer_plans(cfg: ModelConfig, *, decoder: bool = True) -> list[LayerPlan]:
    plans = []
    n = cfg.num_layers
    for i in range(n):
        mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
        is_moe = cfg.moe.is_moe_layer(i)
        if mixer == "attn" and cfg.attention is not None:
            window = cfg.attention.layer_window(i)
        else:
            window = 0
        d_ff = cfg.d_ff
        if (not is_moe and cfg.moe.num_experts and i < cfg.moe.first_k_dense
                and cfg.moe.first_dense_ff):
            d_ff = cfg.moe.first_dense_ff
        mlp = "moe" if is_moe else ("dense" if d_ff > 0 else "none")
        plans.append(LayerPlan(mixer=mixer, mlp=mlp,
                               window=window, d_ff=d_ff,
                               cross_attn=decoder and cfg.family == "audio"))
    return plans


@dataclasses.dataclass(frozen=True)
class Segment:
    pattern: tuple[LayerPlan, ...]
    repeat: int


def segment_plans(plans: list[LayerPlan], max_period: int = 12) -> list[Segment]:
    segs: list[Segment] = []
    i, n = 0, len(plans)
    while i < n:
        best_p, best_r = 1, 1
        for p in range(1, min(max_period, n - i) + 1):
            r = 1
            while (i + (r + 1) * p <= n
                   and plans[i + r * p: i + (r + 1) * p] == plans[i: i + p]):
                r += 1
            if r > 1 and r * p > best_p * best_r:
                best_p, best_r = p, r
        segs.append(Segment(tuple(plans[i: i + best_p]), best_r))
        i += best_p * best_r
    return segs


# ---------------------------------------------------------------------------
# per-layer specs / apply
# ---------------------------------------------------------------------------

def _layer_specs(cfg: ModelConfig, plan: LayerPlan) -> dict:
    d = cfg.d_model
    specs: dict = {"ln1": L.rmsnorm_specs(d)}
    if plan.mixer == "attn":
        specs["attn"] = attn_lib.attn_specs(cfg.attention, d)
    else:
        specs["mamba"] = mamba_lib.mamba_specs(cfg.ssm, d)
    if plan.cross_attn:
        specs["ln_cross"] = L.rmsnorm_specs(d)
        specs["cross"] = attn_lib.attn_specs(
            dataclasses.replace(cfg.attention, use_rope=False), d)
    if plan.mlp != "none":
        specs["ln2"] = L.rmsnorm_specs(d)
    if plan.mlp == "moe":
        specs["moe"] = moe_lib.moe_specs(d, cfg.moe, cfg.mlp_act)
    elif plan.mlp == "dense":
        specs["mlp"] = L.mlp_specs(d, plan.d_ff, cfg.mlp_act)
    return specs


def _apply_layer(cfg: ModelConfig, parallel: Optional[ParallelConfig],
                 rules: Optional[Rules], plan: LayerPlan, params: Params,
                 h: jax.Array, *, positions, dtype, mode: str,
                 cache: Optional[dict], cur_index, enc_out, enc_positions,
                 causal: bool = True, max_cache_len: int = 0):
    """Returns (h, new_cache, aux)."""
    aux = jnp.float32(0.0)
    new_cache: dict = {}

    def _seq_shard(y):
        # Megatron-SP: pin sub-block outputs back to (batch, seq) sharding
        # so XLA lowers the TP partial-sum as reduce-scatter instead of
        # all-reduce + re-slice (halves activation collective bytes).
        if rules is not None and mode != "decode":
            return constrain(y, rules, "batch", "seq", None)
        return y

    x = L.rmsnorm(params["ln1"], h, cfg.norm_eps)
    if plan.mixer == "attn":
        acfg = cfg.attention
        if mode == "decode":
            if acfg.kind == "mla":
                y, kv = attn_lib.mla_decode(params["attn"], acfg, x,
                                            cache["kv"], cur_index,
                                            dtype=dtype)
            else:
                y, kv = attn_lib.gqa_decode(params["attn"], acfg, x,
                                            cache["kv"], cur_index,
                                            window=plan.window, dtype=dtype)
            new_cache["kv"] = kv
        else:
            if acfg.kind == "mla":
                y = attn_lib.mla_forward(params["attn"], acfg, x, positions,
                                         dtype=dtype, block_kv=cfg.attn_block_kv)
            else:
                y = attn_lib.gqa_forward(params["attn"], acfg, x, positions,
                                         window=plan.window, dtype=dtype,
                                         block_kv=cfg.attn_block_kv,
                                         causal=causal)
            if mode == "prefill":
                # ring-buffer length: the window (SWA) or the decode horizon
                # (defaults to the model max; serving passes the actual
                # horizon so a 32k prefill doesn't allocate a 512k cache)
                horizon = max_cache_len or cfg.max_seq_len
                cache_len = min(_cache_len(cfg, plan),
                                max(horizon, x.shape[1]))
                if acfg.kind == "mla":
                    new_cache["kv"] = attn_lib.mla_prefill_cache(
                        params["attn"], acfg, x, positions, cache_len, dtype)
                else:
                    new_cache["kv"] = attn_lib.gqa_prefill_cache(
                        params["attn"], acfg, x, positions, cache_len, dtype)
    else:
        if mode == "decode":
            y, ssm_cache = mamba_lib.mamba_decode(
                params["mamba"], cfg.ssm, x, cache["ssm"], d_model=cfg.d_model,
                dtype=dtype, norm_eps=cfg.norm_eps)
            new_cache["ssm"] = ssm_cache
        elif mode == "prefill":
            y, ssm_cache = mamba_lib.mamba_forward(
                params["mamba"], cfg.ssm, x, d_model=cfg.d_model, dtype=dtype,
                norm_eps=cfg.norm_eps, return_state=True)
            new_cache["ssm"] = ssm_cache
        else:
            y = mamba_lib.mamba_forward(params["mamba"], cfg.ssm, x,
                                        d_model=cfg.d_model, dtype=dtype,
                                        norm_eps=cfg.norm_eps)
    h = h + _seq_shard(y)

    if plan.cross_attn:
        xq = L.rmsnorm(params["ln_cross"], h, cfg.norm_eps)
        acfg = dataclasses.replace(cfg.attention, use_rope=False)
        if mode == "decode":
            k, v = cache["cross_k"], cache["cross_v"]
            q = jnp.einsum("bsd,dhk->bshk", xq, params["cross"]["wq"].astype(dtype))
            o = attn_lib.attention_ref(
                q, k.astype(dtype), v.astype(dtype),
                q_positions=jnp.zeros((xq.shape[0], 1), jnp.int32),
                kv_positions=jnp.zeros((k.shape[0], k.shape[1]), jnp.int32),
                causal=False)
            y = jnp.einsum("bshk,hkd->bsd", o,
                           params["cross"]["wo"].astype(dtype))
            new_cache["cross_k"], new_cache["cross_v"] = k, v
        else:
            k, v = attn_lib.gqa_kv(params["cross"], acfg, enc_out,
                                   enc_positions, dtype)
            y = attn_lib.gqa_forward(params["cross"], acfg, xq, positions,
                                     window=0, dtype=dtype,
                                     block_kv=cfg.attn_block_kv,
                                     kv_override=(k, v, enc_positions),
                                     causal=False)
            if mode == "prefill":
                new_cache["cross_k"], new_cache["cross_v"] = k, v
        h = h + y

    if plan.mlp == "none":
        return h, new_cache, aux
    x2 = L.rmsnorm(params["ln2"], h, cfg.norm_eps)
    if plan.mlp == "moe":
        y, aux = moe_lib.moe_forward(params["moe"], cfg, x2, rules=rules,
                                     parallel=parallel,
                                     decode=(mode == "decode"), dtype=dtype)
    else:
        y = L.mlp(params["mlp"], x2, cfg.mlp_act, dtype)
    return h + _seq_shard(y), new_cache, aux


def _cache_len(cfg: ModelConfig, plan: LayerPlan) -> int:
    if plan.window > 0:
        return min(plan.window, cfg.max_seq_len)
    return cfg.max_seq_len


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

class Model:
    """Functional model bound to (cfg, parallel, rules)."""

    def __init__(self, cfg: ModelConfig,
                 parallel: Optional[ParallelConfig] = None,
                 rules: Optional[Rules] = None):
        cfg.validate()
        self.cfg = cfg
        self.parallel = parallel or ParallelConfig()
        self.rules = rules
        self.dtype = jnp.dtype(cfg.dtype)
        self.plans = layer_plans(cfg)
        self.segments = segment_plans(self.plans)
        if cfg.encoder_layers:
            enc_cfg = dataclasses.replace(
                cfg, num_layers=cfg.encoder_layers, family="dense",
                moe=dataclasses.replace(cfg.moe, num_experts=0))
            self.enc_plans = layer_plans(enc_cfg, decoder=False)
            self.enc_plans = [dataclasses.replace(p, cross_attn=False)
                              for p in self.enc_plans]
            self.enc_segments = segment_plans(self.enc_plans)
        else:
            self.enc_plans, self.enc_segments = [], []

    # -- specs / init -------------------------------------------------------

    def specs(self) -> dict:
        cfg = self.cfg
        specs: dict = {"embed": L.embed_specs(cfg)}
        specs["segments"] = self._stack_specs(self.segments)
        specs["final_norm"] = L.rmsnorm_specs(cfg.d_model)
        head = L.lm_head_specs(cfg)
        if head:
            specs["lm_head"] = head
        if cfg.family == "audio":
            specs["enc_segments"] = self._stack_specs(self.enc_segments)
            specs["enc_final_norm"] = L.rmsnorm_specs(cfg.d_model)
            specs["dec_pos"] = ParamSpec((cfg.max_seq_len, cfg.d_model),
                                         (None, "embed"), stddev=0.02)
        return specs

    def _stack_specs(self, segments: list[Segment]) -> list:
        out = []
        for seg in segments:
            pattern = tuple(_layer_specs(self.cfg, p) for p in seg.pattern)
            out.append(stack_specs(pattern, seg.repeat))
        return out

    def init(self, key: jax.Array) -> Params:
        """Materialize parameters under ``jit``: with rules, every leaf is
        created directly on its shard (a model too big for one device
        never passes through one). Same values as ``init_params``."""
        specs = self.specs()
        shardings = (tree_shardings(self.rules, specs)
                     if self.rules is not None else None)
        return jax.jit(partial(init_params, specs),
                       out_shardings=shardings)(key)

    def abstract(self, shardings=None) -> Params:
        return abstract_params(self.specs(), shardings)

    # -- embedding ----------------------------------------------------------

    def _embed_inputs(self, params: Params, batch: dict):
        """Returns (h, positions, loss_weights)."""
        cfg = self.cfg
        tok = batch["tokens"]
        h = L.embed(params["embed"], tok, self.dtype, cfg.d_model)
        weights = batch.get("weights")
        if weights is None:
            weights = jnp.ones(tok.shape, jnp.float32)
        if cfg.frontend == "patch_stub":
            patches = batch["patches"].astype(self.dtype)   # (B, Np, d)
            h = jnp.concatenate([patches, h], axis=1)
            weights = jnp.concatenate(
                [jnp.zeros(patches.shape[:2], jnp.float32), weights], axis=1)
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        return h, positions, weights

    # -- stacks -------------------------------------------------------------

    def _run_segments(self, params_segs, segments, h, *, positions, mode,
                      caches=None, cur_index=None, enc_out=None,
                      enc_positions=None, causal=True, max_cache_len=0):
        """Apply all segments; returns (h, new_caches, aux_total)."""
        cfg, parallel, rules = self.cfg, self.parallel, self.rules
        aux_total = jnp.float32(0.0)
        new_caches = []
        for si, seg in enumerate(segments):
            p_stack = params_segs[si]
            c_stack = caches[si] if caches is not None else None

            def body(carry, xs, _seg=seg):
                hh, aux = carry
                p_slice, c_slice = xs
                ncs = []
                for li, plan in enumerate(_seg.pattern):
                    c = c_slice[li] if c_slice is not None else None
                    hh, nc, a = _apply_layer(
                        cfg, parallel, rules, plan, p_slice[li], hh,
                        positions=positions, dtype=self.dtype, mode=mode,
                        cache=c, cur_index=cur_index, enc_out=enc_out,
                        enc_positions=enc_positions, causal=causal,
                        max_cache_len=max_cache_len)
                    ncs.append(nc)
                    aux = aux + a
                return (hh, aux), tuple(ncs)

            if parallel.remat != "none" and mode == "train":
                policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                          if parallel.remat == "dots" else None)
                body = jax.checkpoint(body, policy=policy,
                                      prevent_cse=False)

            if parallel.scan_layers:
                (h, aux_total), nc_stack = jax.lax.scan(
                    body, (h, aux_total), (p_stack, c_stack))
            else:
                # unrolled python loop (cost-analysis calibration + small
                # models): identical math, no while-loop in the HLO
                ncs_all = []
                for r in range(seg.repeat):
                    xs = jax.tree_util.tree_map(lambda x, _r=r: x[_r],
                                                (p_stack, c_stack))
                    (h, aux_total), nc = body((h, aux_total), xs)
                    ncs_all.append(nc)
                nc_stack = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *ncs_all)
            new_caches.append(nc_stack)
        return h, new_caches, aux_total

    # -- public entry points ------------------------------------------------

    def hidden_states(self, params: Params, batch: dict,
                      mode: str = "train", max_cache_len: int = 0):
        """Full-sequence forward to final hidden states.

        Returns (h, weights, caches, aux). caches is None unless prefill.
        """
        cfg = self.cfg
        h, positions, weights = self._embed_inputs(params, batch)
        enc_out = enc_positions = None
        if cfg.family == "audio":
            enc_h = batch["frames"].astype(self.dtype)      # (B, Senc, d)
            enc_pos = jnp.arange(enc_h.shape[1], dtype=jnp.int32)
            enc_h = enc_h + L.sinusoidal_positions(
                enc_h.shape[1], cfg.d_model).astype(self.dtype)
            enc_h, _, _ = self._run_segments(
                params["enc_segments"], self.enc_segments, enc_h,
                positions=enc_pos, mode="train", causal=False)
            enc_out = L.rmsnorm(params["enc_final_norm"], enc_h, cfg.norm_eps)
            enc_positions = enc_pos
            h = h + params["dec_pos"][positions].astype(self.dtype)
        if self.rules is not None:
            h = constrain(h, self.rules, "batch", "seq", None)
        h, caches, aux = self._run_segments(
            params["segments"], self.segments, h, positions=positions,
            mode=mode, enc_out=enc_out, enc_positions=enc_positions,
            max_cache_len=max_cache_len)
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        return h, weights, (caches if mode == "prefill" else None), aux

    def logits_fn(self, params: Params):
        cfg = self.cfg
        def fn(h):
            return L.lm_head(params.get("lm_head"), params["embed"], h,
                             cfg.tie_embeddings, self.dtype)
        return fn

    def loss(self, params: Params, batch: dict):
        """Mean cross-entropy (+ z-loss + MoE aux). Returns (loss, metrics)."""
        cfg = self.cfg
        h, weights, _, aux = self.hidden_states(params, batch, mode="train")
        labels = batch["labels"]
        if cfg.frontend == "patch_stub":
            pad = h.shape[1] - labels.shape[1]
            labels = jnp.pad(labels, ((0, 0), (pad, 0)))
        z = getattr(self, "z_loss", 1e-4)
        total, wsum = L.softmax_xent_chunked(
            self.logits_fn(params), h, labels, weights, z_loss=z)
        xent = total / jnp.maximum(wsum, 1.0)
        loss = xent + aux
        return loss, {"loss": loss, "xent": xent, "aux": aux,
                      "tokens": wsum}

    def forward_logits(self, params: Params, batch: dict) -> jax.Array:
        """(B, S, V) logits — for small-model evaluation/serving only."""
        h, _, _, _ = self.hidden_states(params, batch, mode="train")
        return self.logits_fn(params)(h)

    def prefill(self, params: Params, batch: dict,
                max_cache_len: int = 0):
        """Run the prompt, build caches. Returns (last_logits, caches).

        ``max_cache_len`` sizes the full-attention ring buffers (the decode
        horizon); 0 means the model's max context."""
        h, _, caches, _ = self.hidden_states(params, batch, mode="prefill",
                                             max_cache_len=max_cache_len)
        logits = self.logits_fn(params)(h[:, -1:])
        return logits[:, 0], caches

    def decode_step(self, params: Params, caches, tokens: jax.Array,
                    cur_index):
        """One decode step. tokens: (B,) int32; cur_index: scalar position.

        Returns (logits (B, V), new_caches).
        """
        cfg = self.cfg
        h = L.embed(params["embed"], tokens[:, None], self.dtype, cfg.d_model)
        if cfg.family == "audio":
            pos_e = jax.lax.dynamic_slice_in_dim(params["dec_pos"],
                                                 cur_index, 1, axis=0)
            h = h + pos_e[None].astype(self.dtype)
        h, new_caches, _ = self._run_segments(
            params["segments"], self.segments, h, positions=None,
            mode="decode", caches=caches, cur_index=cur_index)
        h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
        logits = self.logits_fn(params)(h)
        return logits[:, 0], new_caches

    # -- cache bootstrap for dry-runs ---------------------------------------

    def init_caches(self, batch: int, prompt_len: int) -> Any:
        """Concrete zero caches sized for a `prompt_len` context."""
        cfg = self.cfg
        caches = []
        for seg in self.segments:
            pattern_caches = []
            for plan in seg.pattern:
                c: dict = {}
                if plan.mixer == "attn":
                    clen = min(_cache_len(cfg, plan), max(prompt_len, 1))
                    if cfg.attention.kind == "mla":
                        c["kv"] = attn_lib.mla_cache_init(
                            cfg.attention, batch, clen, self.dtype)
                    else:
                        c["kv"] = attn_lib.gqa_cache_init(
                            cfg.attention, batch, clen, self.dtype)
                else:
                    c["ssm"] = mamba_lib.mamba_cache_init(
                        cfg.ssm, batch, cfg.d_model, self.dtype)
                if plan.cross_attn:
                    a = cfg.attention
                    c["cross_k"] = jnp.zeros(
                        (batch, cfg.encoder_seq, a.num_kv_heads, a.head_dim),
                        self.dtype)
                    c["cross_v"] = jnp.zeros_like(c["cross_k"])
                pattern_caches.append(c)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None],
                                           (seg.repeat,) + x.shape),
                tuple(pattern_caches))
            caches.append(stacked)
        return caches
