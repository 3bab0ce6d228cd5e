"""Unified model facade: one API over all 10 architecture families.

    model = Model(cfg, plan)
    meta   = model.param_meta()                    # ParamMeta tree
    params = model.init(key)                       # materialized (smoke/CPU)
    logits, aux = model.apply(params, batch)       # train forward
    logits, cache = model.prefill(params, batch)   # serve: prefill
    logits, cache = model.decode(params, tok, cache, pos)

``batch`` is a dict: tokens (B,S) [+ labels], image_embeds (vlm),
audio_frames (audio). Frontends for vlm/audio are stubs per the assignment.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import multimodal as mm
from repro.models import params as pm
from repro.models import transformer as tf
from repro.models.layers import cdt
from repro.sharding.plan import Plan, make_plan


class Model:
    def __init__(self, cfg: ModelConfig, plan: Optional[Plan] = None):
        self.cfg = cfg
        self.plan = plan or make_plan(cfg, None)

    # --- params -----------------------------------------------------------
    def param_meta(self):
        cfg, plan = self.cfg, self.plan
        if cfg.family == "vlm":
            return mm.vlm_params(cfg, plan)
        if cfg.family == "audio":
            return mm.whisper_params(cfg, plan)
        return tf.lm_params(cfg, plan)

    def init(self, key):
        return pm.materialize(self.param_meta(), key, self.cfg.param_dtype)

    def abstract_params(self):
        return pm.abstract(self.param_meta(), self.cfg.param_dtype)

    def n_params(self) -> int:
        return pm.n_params(self.param_meta())

    # --- forward ------------------------------------------------------------
    def apply(self, params, batch: Dict[str, Any]):
        cfg, plan = self.cfg, self.plan
        tokens = batch["tokens"]
        if cfg.family == "vlm":
            return mm.vlm_apply(params, tokens, batch["image_embeds"], cfg, plan)
        if cfg.family == "audio":
            return mm.whisper_apply(params, tokens, batch["audio_frames"], cfg, plan)
        return tf.lm_apply(params, tokens, cfg, plan)

    # --- serving ------------------------------------------------------------
    def prefill(self, params, batch: Dict[str, Any], max_len: Optional[int] = None,
                lengths=None):
        cfg, plan = self.cfg, self.plan
        tokens = batch["tokens"]
        if cfg.family == "vlm":
            return mm.vlm_prefill(params, tokens, batch["image_embeds"], cfg,
                                  plan, max_len, lengths=lengths)
        if cfg.family == "audio":
            return mm.whisper_prefill(params, tokens, batch["audio_frames"],
                                      cfg, plan, max_len, lengths=lengths)
        return tf.lm_prefill(params, tokens, cfg, plan, max_len, lengths=lengths)

    def decode(self, params, tokens, cache, pos, n_valid=None):
        """Ragged decode: ``pos`` scalar or (B,) per-slot; tokens (B,S), S>=1.

        ``n_valid`` (B,) marks real tokens per row for chunked-prefill
        extends (attention families; SSM/hybrid state ignores it).
        """
        cfg, plan = self.cfg, self.plan
        if cfg.family == "vlm":
            return mm.vlm_decode(params, tokens, cache, pos, cfg, plan,
                                 n_valid=n_valid)
        if cfg.family == "audio":
            return mm.whisper_decode(params, tokens, cache, pos, cfg, plan,
                                     n_valid=n_valid)
        return tf.lm_decode(params, tokens, cache, pos, cfg, plan,
                            n_valid=n_valid)[:2]

    @property
    def decodes_in_pool(self) -> bool:
        """Whether :meth:`decode_paged` applies: every cache of the stack
        holds full-length attention entries (GQA K/V or MLA latents)."""
        return tf.paged_decode_applies(self.cfg)

    def decode_paged(self, params, tokens, pool, bt, pos, n_valid=None):
        """:meth:`decode` against a page pool of full-length attention
        caches (``pool`` as ``PagedKVCacheManager`` holds it, ``bt`` the
        block tables): entries stay in their pages, each layer writes only
        the new ones.  Returns (logits, updated pool, stats), ``stats`` the
        expert layers' counters summed over layers (``routed_local``,
        ``experts_touched``; empty without experts)."""
        return tf.lm_decode(params, tokens, pool, pos, self.cfg, self.plan,
                            n_valid=n_valid, bt=bt)

    # --- caches ---------------------------------------------------------------
    def cache(self, batch_size: int, max_len: int, abstract: bool = False):
        cfg, plan = self.cfg, self.plan
        dtype = cdt(cfg)
        if cfg.family == "vlm":
            return mm.vlm_cache(cfg, plan, batch_size, max_len, dtype, abstract)
        if cfg.family == "audio":
            return mm.whisper_cache(cfg, plan, batch_size, max_len, dtype, abstract)
        return tf.lm_cache(cfg, plan, batch_size, max_len, dtype, abstract)

    def cache_specs(self, seq_axis=None):
        cfg, plan = self.cfg, self.plan
        if cfg.family == "vlm":
            return mm.vlm_cache_specs(cfg, plan, seq_axis)
        if cfg.family == "audio":
            return mm.whisper_cache_specs(cfg, plan, seq_axis)
        return tf.lm_cache_specs(cfg, plan, seq_axis)
