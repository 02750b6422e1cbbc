"""The mesh-sharded inference engine — serving twin of ``train.Engine``.

One :class:`InferenceEngine` owns what the standalone decode loop in the
old ``launch/serve.py`` hand-rolled (unsharded, random params, no slot
reuse):

  * the logical-axis rule tables from ``distributed/sharding.py`` resolved
    into ``in_shardings``/``out_shardings`` for the whole
    :class:`InferenceState` — params under the same placement training
    used, cache leaves through ``cache_axes`` where the ``cache_seq`` rule
    takes the ``cache_needs_seq_shard`` branch;
  * a jitted, donated prefill-insert step: prefill ONE request at its
    exact prompt length (no padding, so recurrent/SSM state is exact) and
    scatter its cache into a free slot of the slot-major state;
  * a jitted, donated decode step over ALL slots at once, each advancing
    its own position counter (ragged prompt lengths coexist in one batch);
  * optionally a PAGED cache (``paged=True``): a fixed-size page pool +
    per-slot page tables decouple slot count from ``max_len`` (KV memory
    follows live tokens), and ``prefill_chunk`` admits long prompts chunk
    by chunk through ``insert_chunk`` so the scheduler can interleave
    admission with decode — the contiguous layout stays available as the
    parity baseline;
  * the trained-checkpoint hand-off: ``from_train_state`` adopts a live
    ``TrainState.params`` without gathering to host, and
    ``restore_params`` rebuilds only the params subtree of a TrainState
    .npz (optimizer moments are never instantiated).

Slot allocation / EOS eviction policy lives in ``serve.scheduler``; the
engine is policy-free and model-agnostic across every
``cfg.supports_decode()`` architecture.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

import numpy as np

from repro import checkpoint as ckpt
from repro.configs.base import ModelConfig
from repro.obs import profiler
from repro.distributed.sharding import (
    logical_sharding, make_rules, resolve_pspec, tree_shardings,
)
from repro.models import transformer as tfm
from repro.serve import sampling
from repro.serve.state import (
    InferenceState, clear_pages, concat_page_rows, copy_pool_pages,
    gather_page_rows, gather_slot_rows, inference_state_axes, is_axes,
    new_inference_state, new_paged_inference_state,
    paged_inference_state_axes, scatter_page_rows, scatter_slot,
    scatter_slot_rows, select_verified,
)


class InferenceEngine:
    """Sharded, donated prefill/decode step factory over request slots.

    ``paged=True`` swaps the slot-major KV rings for a page pool + per-slot
    page tables (slot count decoupled from ``max_len``; ``num_pages`` sizes
    KV memory to live tokens) and unlocks ``prefill_chunk``: long prompts
    are inserted ``prefill_chunk`` tokens at a time via :meth:`insert_chunk`
    so the scheduler can interleave admission with fused decode steps."""

    def __init__(self, cfg: ModelConfig, *, mesh=None, slots: int = 4,
                 max_len: int = 64, dtype=jnp.bfloat16,
                 rules: Optional[dict] = None, donate: bool = True,
                 explicit_shardings: bool = True, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: int = 0):
        if not cfg.supports_decode():
            raise ValueError(f"{cfg.name} has no decode path")
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.dtype = dtype
        self.donate = donate
        self.paged = bool(paged)
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk and not self.paged:
            raise ValueError("prefill_chunk requires the paged cache "
                             "(chunks are written into page tables)")
        # mesh and rules are built LAZILY, mirroring train.Engine: never
        # touch jax device state before the launcher injects XLA_FLAGS
        self._mesh = mesh
        self._rules = rules
        self._explicit = explicit_shardings
        if self.paged:
            if page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {page_size}")
            self.page_size = int(page_size)
            self.pages_per_slot = -(-self.max_len // self.page_size)
            self.num_pages = int(num_pages) if num_pages \
                else self.slots * self.pages_per_slot
            self._axes = paged_inference_state_axes(cfg)
            self._cache_axes = tfm.paged_cache_axes(cfg)
        else:
            self.page_size = self.pages_per_slot = self.num_pages = None
            self._axes = inference_state_axes(cfg)
            self._cache_axes = tfm.cache_axes(cfg)
        self._jit_cache: dict = {}
        self._state_shardings = None
        self._has_rec: Optional[bool] = None

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_host_mesh
            self._mesh = make_host_mesh()
        return self._mesh

    @property
    def rules(self) -> dict:
        if self._rules is None:
            self._rules = make_rules(self.cfg, mesh=self.mesh)
        return self._rules

    # -- state lifecycle ---------------------------------------------------
    def init_state(self, params: Any) -> InferenceState:
        """Fresh InferenceState around ``params``, placed on its shardings.

        Takes OWNERSHIP of ``params`` (the buffers are donated through the
        jitted steps): when handing off a live TrainState the training side
        must be done with it, and when the shardings already match — the
        ``from_train_state`` path — the device_put is a no-op and the
        weights never return to host."""
        if self.paged:
            state = new_paged_inference_state(
                params, self.cfg, slots=self.slots, num_pages=self.num_pages,
                pages_per_slot=self.pages_per_slot, page_size=self.page_size,
                dtype=self.dtype)
        else:
            state = new_inference_state(params, self.cfg, slots=self.slots,
                                        max_len=self.max_len,
                                        dtype=self.dtype)
        if self._explicit:
            state = jax.device_put(state, self.state_shardings(state))
        return state

    def assign_pages(self, state: InferenceState, slot: int, pages,
                     fresh=None) -> InferenceState:
        """Install ``pages`` (an ordered list of physical page ids from the
        scheduler's free list) as ``slot``'s page row, and reset the
        position metadata of the FRESH ones in every layer pool — a page
        recycled from an evicted request must never leak stale entries
        into its new owner's attention mask.  ``fresh`` defaults to all of
        ``pages``; a prefix-cache admission passes only its newly-claimed
        pages so the shared run's cached entries survive the install.
        Host-side policy hook, outside the jitted steps."""
        assert self.paged, "assign_pages is a paged-mode operation"
        row = np.full((self.pages_per_slot,), -1, np.int32)
        row[:len(pages)] = pages
        table = state.page_table.at[slot].set(jnp.asarray(row))
        clear = list(pages) if fresh is None else list(fresh)
        cache = state.cache
        if clear:
            cache = clear_pages(self._cache_axes, cache,
                                jnp.asarray(clear, jnp.int32),
                                self.num_pages)
        if self._explicit:
            # re-place only what this host-side update touched — the params
            # subtree (hundreds of leaves) is untouched and stays put
            sh = self.state_shardings(state)
            cache = jax.device_put(cache, sh.cache)
            table = jax.device_put(table, sh.page_table)
        return state._replace(cache=cache, page_table=table)

    @property
    def has_recurrent_state(self) -> bool:
        """True when the arch keeps slot-major recurrent/SSM cache leaves
        alongside the paged KV pools.  Pages hold only attention KV, so a
        prefix-cache hit on such an arch must restore the recurrent state
        at the resume offset from a host-side snapshot (the radix cache
        stores one per registered page boundary)."""
        if self._has_rec is None:
            axes = jax.tree.leaves(self._cache_axes, is_leaf=is_axes)
            self._has_rec = any("batch" in a for a in axes) if self.paged \
                else True
        return self._has_rec

    def copy_pages(self, state: InferenceState, src, dst) -> InferenceState:
        """Copy-on-write: clone pool pages ``src`` into ``dst`` across
        every paged KV leaf (k, v and pos).  The scheduler calls this when
        an admission must write into a page whose refcount it does not
        exclusively own — the write lands in the private ``dst`` copy and
        the shared original stays immutable for its other readers."""
        assert self.paged, "copy_pages is a paged-mode operation"
        cache = copy_pool_pages(self._cache_axes, state.cache, src, dst)
        if self._explicit:
            cache = jax.device_put(cache, self.state_shardings(state).cache)
        return state._replace(cache=cache)

    def get_slot_state(self, state: InferenceState, slot: int) -> list:
        """Host snapshot of ``slot``'s recurrent/SSM rows (leaf-aligned,
        ``None`` per paged KV leaf) — what the prefix cache stores per
        registered page boundary so a later hit can resume mid-prompt."""
        assert self.paged, "get_slot_state is a paged-mode operation"
        return gather_slot_rows(self._cache_axes, state.cache, int(slot))

    def set_slot_state(self, state: InferenceState, slot: int,
                       rows: list) -> InferenceState:
        """Restore a ``get_slot_state`` snapshot into ``slot`` (any slot)."""
        assert self.paged, "set_slot_state is a paged-mode operation"
        cache = scatter_slot_rows(self._cache_axes, state.cache, int(slot),
                                  rows)
        if self._explicit:
            cache = jax.device_put(cache, self.state_shardings(state).cache)
        return state._replace(cache=cache)

    def _install_sampling(self, state: InferenceState, slot: int,
                          temp: float, top_k: int, top_p: float, rep: float,
                          key, presence) -> InferenceState:
        """Write one slot's sampling rows (host-side policy hook shared by
        ``set_sampling`` and ``swap_in``), re-placing only what changed."""
        state = state._replace(
            sample_temp=state.sample_temp.at[slot].set(float(temp)),
            sample_top_k=state.sample_top_k.at[slot].set(int(top_k)),
            sample_top_p=state.sample_top_p.at[slot].set(float(top_p)),
            sample_rep=state.sample_rep.at[slot].set(float(rep)),
            sample_key=state.sample_key.at[slot].set(
                jnp.asarray(key, jnp.uint32)),
            tok_presence=state.tok_presence.at[slot].set(
                jnp.asarray(presence, bool)),
        )
        if self._explicit:
            sh = self.state_shardings(state)
            state = state._replace(
                sample_temp=jax.device_put(state.sample_temp,
                                           sh.sample_temp),
                sample_top_k=jax.device_put(state.sample_top_k,
                                            sh.sample_top_k),
                sample_top_p=jax.device_put(state.sample_top_p,
                                            sh.sample_top_p),
                sample_rep=jax.device_put(state.sample_rep, sh.sample_rep),
                sample_key=jax.device_put(state.sample_key, sh.sample_key),
                tok_presence=jax.device_put(state.tok_presence,
                                            sh.tok_presence),
            )
        return state

    def set_sampling(self, state: InferenceState, slot: int,
                     params: "sampling.SamplingParams",
                     context=()) -> InferenceState:
        """Install a request's :class:`~repro.serve.sampling.SamplingParams`
        into ``slot``'s per-slot arrays at admission: parameters, the
        seed-derived base PRNG key, and the repetition-penalty presence
        row seeded with ``context`` (the full prompt — also on a
        prefix-cache resume, so the mask never depends on the resume
        offset).  Host-side policy hook, outside the jitted steps."""
        params.validate()
        return self._install_sampling(
            state, int(slot), params.temperature, params.top_k,
            params.top_p, params.rep_penalty,
            sampling.base_key(params.seed),
            sampling.presence_row(context, self.cfg.padded_vocab()))

    def swap_out(self, state: InferenceState, slot: int, pages) -> dict:
        """Page-aware preemption, out half: ``jax.device_get`` of JUST the
        victim's pool rows (every paged KV leaf at ``pages``) plus its
        slot-major recurrent rows and counters.  Together with the host-
        side request (prompt + generated tokens) the blob is the complete
        resume state; the pages and the slot can be handed to another
        request immediately."""
        assert self.paged, "swap_out is a paged-mode operation"
        slot = int(slot)
        return {
            "kv": gather_page_rows(self._cache_axes, state.cache, pages),
            "rec": gather_slot_rows(self._cache_axes, state.cache, slot),
            "pos": int(jax.device_get(state.positions[slot])),
            "last_tok": int(jax.device_get(state.last_tok[slot])),
            # sampling travels in the blob so a restored request keeps
            # drawing the exact stream it was preempted from (the base
            # key plus the restored position counter reproduce the folds)
            "samp": {
                "temp": float(jax.device_get(state.sample_temp[slot])),
                "top_k": int(jax.device_get(state.sample_top_k[slot])),
                "top_p": float(jax.device_get(state.sample_top_p[slot])),
                "rep": float(jax.device_get(state.sample_rep[slot])),
                "key": np.asarray(jax.device_get(state.sample_key[slot])),
                "presence": np.asarray(
                    jax.device_get(state.tok_presence[slot])),
            },
        }

    def swap_in(self, state: InferenceState, slot: int, pages,
                blob: dict) -> InferenceState:
        """Restore a ``swap_out`` blob into ``slot`` over freshly-claimed
        ``pages`` (same count and order as the swap-out run; the physical
        ids may differ — page contents are keyed by absolute position).
        The victim resumes decoding exactly where it was preempted."""
        assert self.paged, "swap_in is a paged-mode operation"
        with profiler.annotate("serve.swap_in"):
            return self._swap_in(state, slot, pages, blob)

    def _swap_in(self, state: InferenceState, slot: int, pages,
                 blob: dict) -> InferenceState:
        state = self.assign_pages(state, slot, pages)
        cache = scatter_page_rows(self._cache_axes, state.cache, pages,
                                  blob["kv"])
        cache = scatter_slot_rows(self._cache_axes, cache, int(slot),
                                  blob["rec"])
        positions = state.positions.at[slot].set(blob["pos"])
        last_tok = state.last_tok.at[slot].set(blob["last_tok"])
        if self._explicit:
            sh = self.state_shardings(state)
            cache = jax.device_put(cache, sh.cache)
            positions = jax.device_put(positions, sh.positions)
            last_tok = jax.device_put(last_tok, sh.last_tok)
        state = state._replace(cache=cache, positions=positions,
                               last_tok=last_tok)
        samp = blob["samp"]
        return self._install_sampling(
            state, int(slot), samp["temp"], samp["top_k"], samp["top_p"],
            samp["rep"], samp["key"], samp["presence"])

    def spill_page(self, state: InferenceState, page: int) -> list:
        """Host copy of ONE pool page across every paged KV leaf — what
        the radix cache's host tier stores when ``_reclaim`` evicts a
        cached (ref-0) page under pool pressure.  Leaf-aligned like
        ``gather_page_rows`` (``None`` on slot-major leaves); the pos
        leaf travels in the blob, so the content stays keyed by absolute
        stream positions, never by the physical page id."""
        assert self.paged, "spill_page is a paged-mode operation"
        return gather_page_rows(self._cache_axes, state.cache, [int(page)])

    def restore_pages(self, state: InferenceState, pages,
                      blobs: list) -> InferenceState:
        """Scatter per-page spill blobs (one :meth:`spill_page` blob per
        entry of ``pages``, in order) back into freshly-claimed pool
        pages — the restore half of a host-tier prefix hit: the KV those
        pages held returns by a host-to-device copy instead of prefill
        compute.  The physical ids may differ from the spill-time ones;
        page contents are keyed by the absolute positions in the pos
        leaf, exactly like a preemption ``swap_in``."""
        assert self.paged, "restore_pages is a paged-mode operation"
        with profiler.annotate("serve.restore_pages"):
            rows = concat_page_rows(self._cache_axes, blobs)
            cache = scatter_page_rows(self._cache_axes, state.cache, pages,
                                      rows)
            if self._explicit:
                cache = jax.device_put(cache,
                                       self.state_shardings(state).cache)
            return state._replace(cache=cache)

    def release_pages(self, state: InferenceState,
                      slot: int) -> InferenceState:
        """Clear ``slot``'s page row on eviction.  The freed pages may be
        handed to another request immediately, and a cleared row (-1)
        turns any later write through this slot — e.g. a mask-free
        ``decode(state)`` — into a dropped out-of-bounds scatter instead
        of a silent write into the new owner's pages."""
        assert self.paged, "release_pages is a paged-mode operation"
        return state._replace(page_table=state.page_table.at[slot].set(-1))

    @classmethod
    def from_train_state(cls, train_engine, train_state, *, slots: int = 4,
                         max_len: int = 64, dtype=jnp.bfloat16,
                         **kw) -> tuple["InferenceEngine", InferenceState]:
        """Adopt a trained ``TrainState`` from a ``train.Engine`` in place.

        The inference engine reuses the train engine's mesh; its rule table
        resolves the params to the same NamedShardings training used (the
        fsdp variant re-gathers shard-to-shard on device), so the returned
        InferenceState is built without a host round-trip.  The train state
        must not be stepped afterwards — its params are donated here."""
        eng = cls(train_engine.cfg, mesh=train_engine.mesh, slots=slots,
                  max_len=max_len, dtype=dtype, **kw)
        return eng, eng.init_state(train_state.params)

    def restore_params(self, path: str, example_params: Any) -> Any:
        """Params subtree of a full-TrainState .npz, restored into
        ``example_params`` — the CLI hand-off (``--ckpt`` from
        ``repro.launch.train``) without touching optimizer moments."""
        return ckpt.restore_subtree(path, example_params, prefix="params")

    # -- sharding resolution -----------------------------------------------
    def state_shardings(self, state: InferenceState) -> InferenceState:
        """NamedSharding tree matching ``state`` from the rule tables.
        Cached after the first resolution — the engine's state shapes are
        fixed, and admissions (``assign_pages``) re-place the state on
        every request."""
        if self._state_shardings is None:
            self._state_shardings = tree_shardings(self._axes, state,
                                                   self.mesh, self.rules)
        return self._state_shardings

    def _input_shardings(self, inputs: Dict[str, jax.Array]):
        out = {}
        for k, v in inputs.items():
            axes = ("batch",) + (None,) * (jnp.ndim(v) - 1)
            out[k] = NamedSharding(self.mesh, resolve_pspec(
                axes, jnp.shape(v), self.mesh, self.rules))
        return out

    # -- the steps ---------------------------------------------------------
    def _sample_args(self, state: InferenceState) -> dict:
        return dict(keys=state.sample_key, temperature=state.sample_temp,
                    top_k=state.sample_top_k, top_p=state.sample_top_p,
                    rep_penalty=state.sample_rep)

    @jax.named_scope("logits")        # sampling, beside the projection
    def _sample_one(self, state: InferenceState, logits: jax.Array,
                    slot: jax.Array, pos) -> jax.Array:
        """First-token emission for one slot (prefill / final chunk):
        argmax when the slot is greedy, else a draw at absolute stream
        position ``pos`` under the slot's own parameters.  No presence
        fold — the prompt's presence was installed host-side at admission
        (``set_sampling``) and the emitted token folds in at the step
        that consumes it."""
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)       # (1,)

        def _go():
            toks = sampling.draw(
                logits, keys=state.sample_key[slot][None],
                positions=jnp.asarray(pos, jnp.int32)[None],
                temperature=state.sample_temp[slot][None],
                top_k=state.sample_top_k[slot][None],
                top_p=state.sample_top_p[slot][None],
                rep_penalty=state.sample_rep[slot][None],
                presence=state.tok_presence[slot][None])
            return toks
        return jax.lax.cond(state.sample_temp[slot] > 0, _go,
                            lambda: greedy)

    @jax.named_scope("logits")        # sampling, beside the projection
    def _sample_all(self, state: InferenceState, logits: jax.Array,
                    positions: jax.Array, active=None):
        """All-slot emission for the fused decode: (tokens (S,), presence).
        The single ``lax.cond`` keeps an all-greedy batch bit-identical
        to (and as cheap as) the bare argmax path; otherwise every slot
        first folds the input token it just consumed (``last_tok``) into
        its presence row, then draws with its position-folded key —
        greedy slots still take the raw argmax."""
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)       # (S,)

        def _go():
            S = logits.shape[0]
            pres = state.tok_presence.at[
                jnp.arange(S), state.last_tok].set(True)
            if active is not None:
                pres = jnp.where(active[:, None], pres, state.tok_presence)
            toks = sampling.draw(logits, positions=positions, presence=pres,
                                 **self._sample_args(state))
            return jnp.where(state.sample_temp > 0, toks, greedy), pres
        return jax.lax.cond(jnp.any(state.sample_temp > 0), _go,
                            lambda: (greedy, state.tok_presence))

    def _insert_fn(self, state: InferenceState, inputs: Dict[str, jax.Array],
                   slot: jax.Array):
        logits, cache_one = tfm.prefill(state.params, self.cfg, inputs,
                                        max_len=self.max_len,
                                        dtype=self.dtype)
        total = inputs["tokens"].shape[1] + (
            inputs["patches"].shape[1] if "patches" in inputs else 0)
        tok = self._sample_one(state, logits, slot, total)      # (1,)
        if self.paged:
            # same exact-length prefill; the ring cache scatters into the
            # slot's pages instead of a slot row
            cache = tfm.scatter_prefill_paged(
                self.cfg, state.cache, cache_one, state.page_table[slot],
                slot)
        else:
            cache = scatter_slot(self._cache_axes, state.cache, cache_one,
                                 slot)
        return state._replace(
            cache=cache,
            positions=state.positions.at[slot].set(total),
            last_tok=state.last_tok.at[slot].set(tok[0]),
        ), tok

    def _chunk_fn(self, state: InferenceState, inputs: Dict[str, jax.Array],
                  slot: jax.Array, pos_start: jax.Array):
        logits, cache, counts = tfm.prefill_chunk(
            state.params, self.cfg, inputs, state.cache,
            state.page_table[slot], slot, pos_start, dtype=self.dtype)
        end = pos_start + inputs["tokens"].shape[1]
        # only the final chunk's token is kept, and ``end`` is then the
        # same absolute position a whole-prompt insert would fold — the
        # draw is invariant under chunking
        tok = self._sample_one(state, logits, slot, end)        # (1,)
        return state._replace(
            cache=cache,
            positions=state.positions.at[slot].set(end),
            last_tok=state.last_tok.at[slot].set(tok[0]),
            expert_counts=counts,
        ), tok

    def _decode_fn(self, state: InferenceState):
        logits, cache = tfm.decode_step(
            state.params, self.cfg, {"tokens": state.last_tok[:, None]},
            state.cache, state.positions, dtype=self.dtype)
        tok, presence = self._sample_all(state, logits,
                                         state.positions + 1)  # (slots,)
        return state._replace(cache=cache, positions=state.positions + 1,
                              last_tok=tok, tok_presence=presence), tok

    def _decode_paged_fn(self, state: InferenceState, active: jax.Array):
        logits, cache, counts = tfm.decode_step_paged(
            state.params, self.cfg, {"tokens": state.last_tok[:, None]},
            state.cache, state.positions, state.page_table, active,
            dtype=self.dtype)
        tok, presence = self._sample_all(state, logits,
                                         state.positions + 1, active)
        return state._replace(
            cache=cache,
            positions=state.positions + active.astype(jnp.int32),
            last_tok=jnp.where(active, tok, state.last_tok),
            tok_presence=presence,
            expert_counts=counts,
        ), tok

    def _verify_fn(self, state: InferenceState, drafts: jax.Array,
                   draft_len: jax.Array, active: jax.Array):
        """One fused speculative step: feed each active slot its last token
        plus ``drafts`` (S, K) proposed tokens, verify in ONE paged forward,
        and accept the longest prefix of drafts matching the model's OWN
        next tokens — the raw argmax for greedy slots, a position-keyed
        draw from the (penalized/filtered) target distribution for
        sampled slots.

        Losslessness: for a greedy slot this is the classic greedy
        prefix-match.  For a sampled slot it is rejection-sampling
        verification specialized to a DETERMINISTIC drafter (the draft
        distribution is a point mass, so ``min(1, p/q)`` acceptance +
        residual resampling collapses to: draw ``t_i`` from the target at
        position ``i`` with that position's folded key, accept the draft
        iff it equals ``t_i``, and emit ``t_i`` either way) — the emitted
        stream is therefore BIT-IDENTICAL to the non-speculative sampled
        stream at equal seeds, not merely equal in distribution.  Rejected
        KV writes are shadowed by the positional mask, and recurrent/SSM
        state rolls back to the per-step snapshot at the last accepted
        token."""
        S, K = drafts.shape
        toks = jnp.concatenate([state.last_tok[:, None], drafts], axis=1)
        logits, stacked, counts = tfm.verify_step_paged(
            state.params, self.cfg, {"tokens": toks}, state.cache,
            state.positions, state.page_table, active, dtype=self.dtype)
        greedy = jnp.argmax(logits, -1).astype(jnp.int32)       # (S, K+1)
        any_sampled = jnp.any(state.sample_temp > 0)
        ar_s = jnp.arange(S)

        def _sampled_targets():
            # walk the K+1 positions in order, folding each INPUT token
            # into presence before drawing its successor — the same
            # presence/position alignment K+1 successive decode steps
            # would produce, which is what makes spec == non-spec exact
            pres = state.tok_presence
            cols = []
            for i in range(K + 1):
                pres = pres.at[ar_s, toks[:, i]].set(True)
                t = sampling.draw(logits[:, i],
                                  positions=state.positions + i + 1,
                                  presence=pres,
                                  **self._sample_args(state))
                cols.append(jnp.where(state.sample_temp > 0, t,
                                      greedy[:, i]))
            return jnp.stack(cols, axis=1)
        target = jax.lax.cond(any_sampled, _sampled_targets,
                              lambda: greedy)                   # (S, K+1)
        ar = jnp.arange(K, dtype=jnp.int32)[None, :]
        match = (target[:, :-1] == drafts) & (ar < draft_len[:, None])
        # accepted drafts = longest matching prefix; emitted = accepted + 1
        # (the model's own next token after the last accepted position)
        n = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        consumed = jnp.where(active, n + 1, 0).astype(jnp.int32)
        cache = select_verified(self._cache_axes, stacked, state.cache, n,
                                active)
        last = jnp.take_along_axis(target, n[:, None], axis=1)[:, 0]

        def _commit_presence():
            # fold exactly the inputs this step consumed (j < consumed):
            # the rejected tail must not poison the repetition mask
            pres = state.tok_presence
            for j in range(K + 1):
                upd = pres.at[ar_s, toks[:, j]].set(True)
                pres = jnp.where(((j < consumed) & active)[:, None],
                                 upd, pres)
            return pres
        presence = jax.lax.cond(any_sampled, _commit_presence,
                                lambda: state.tok_presence)
        return state._replace(
            cache=cache,
            positions=state.positions + consumed,
            last_tok=jnp.where(active, last, state.last_tok),
            tok_presence=presence,
            expert_counts=counts,
        ), target, consumed

    def _active_sharding(self):
        return NamedSharding(self.mesh, resolve_pspec(
            ("batch",), (self.slots,), self.mesh, self.rules))

    def _get_jit(self, kind: str, state, inputs=None):
        key = (kind,) + (tuple(sorted(
            (k, tuple(jnp.shape(v)), str(v.dtype))
            for k, v in inputs.items())) if inputs else ())
        jfn = self._jit_cache.get(key)
        if jfn is None:
            fns = {"insert": self._insert_fn, "chunk": self._chunk_fn,
                   "decode": self._decode_fn,
                   "decode_paged": self._decode_paged_fn,
                   "verify": self._verify_fn}
            fn = fns[kind]
            donate = (0,) if self.donate else ()
            if not self._explicit:
                jfn = jax.jit(fn, donate_argnums=donate)
            else:
                st_sh = self.state_shardings(state)
                if kind == "insert":
                    in_sh = (st_sh, self._input_shardings(inputs), None)
                elif kind == "chunk":
                    in_sh = (st_sh, self._input_shardings(inputs), None, None)
                elif kind == "decode":
                    in_sh = (st_sh,)
                elif kind == "verify":
                    in_sh = (st_sh, self._input_shardings(inputs)["drafts"],
                             self._active_sharding(),
                             self._active_sharding())
                else:
                    in_sh = (st_sh, self._active_sharding())
                out_sh = (st_sh, None, None) if kind == "verify" \
                    else (st_sh, None)
                jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=donate)
            self._jit_cache[key] = jfn
        return jfn

    def _run(self, jfn, *args):
        if not self._explicit:
            return jfn(*args)
        with self.mesh, logical_sharding(self.mesh, self.rules):
            return jfn(*args)

    def insert(self, state: InferenceState, inputs: Dict[str, jax.Array],
               slot: int):
        """Prefill ONE request (tokens (1, L), exact length — plus patches
        for VLM archs) into slot ``slot``.  Returns (state, first greedy
        token (1,)).  Jit-cached per distinct prompt shape.  In paged mode
        the slot's page row must already be installed (``assign_pages``)."""
        inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
        jfn = self._get_jit("insert", state, inputs)
        # annotate() scopes are live only inside an open jax.profiler
        # window (--profile-dir): host phase names then line up with the
        # device timeline; otherwise they are null contexts
        with profiler.annotate("serve.insert"):
            return self._run(jfn, state, inputs,
                             jnp.asarray(slot, jnp.int32))

    def insert_chunk(self, state: InferenceState,
                     inputs: Dict[str, jax.Array], slot: int,
                     pos_start: int):
        """Insert ONE prompt chunk (tokens (1, C)) starting at absolute
        position ``pos_start`` into slot ``slot``'s pages.  Returns
        (state, greedy token (1,)) — the token is meaningful only for the
        final chunk of a prompt.  Jit-cached per chunk shape, so a prompt
        split into fixed-size chunks compiles twice at most (body +
        remainder).  The returned state's ``expert_counts`` are the
        chunk's."""
        assert self.paged, "insert_chunk requires the paged cache"
        inputs = {k: jnp.asarray(v) for k, v in inputs.items()}
        jfn = self._get_jit("chunk", state, inputs)
        with profiler.annotate("serve.insert_chunk"):
            return self._run(jfn, state, inputs,
                             jnp.asarray(slot, jnp.int32),
                             jnp.asarray(pos_start, jnp.int32))

    def verify(self, state: InferenceState, drafts, draft_len, active):
        """One fused speculative decode step over ALL slots.  ``drafts``
        (slots, K) int32 proposed tokens per slot (row ``s`` meaningful up
        to ``draft_len[s]``; the rest is padding whose cache writes are
        shadowed exactly like rejected drafts); ``active`` (slots,) bool as
        in :meth:`decode`.  Returns (state, emitted (slots, K+1) target
        tokens, consumed (slots,)): slot ``s`` emitted
        ``emitted[s, :consumed[s]]`` — its own continuation under its
        sampling params (argmax for greedy slots), bit-identical to
        ``consumed[s]`` successive :meth:`decode` calls — and advanced
        its position by ``consumed[s]``.  Jit-cached per K.  The returned
        state's ``expert_counts`` are the step's."""
        if not self.paged:
            raise ValueError("speculative verification writes draft KV "
                             "through page tables; build the engine with "
                             "paged=True (the --spec-k 0 contiguous path "
                             "is the parity baseline)")
        drafts = jnp.asarray(drafts, jnp.int32)
        jfn = self._get_jit("verify", state, {"drafts": drafts})
        with profiler.annotate("serve.verify"):
            return self._run(jfn, state, drafts,
                             jnp.asarray(draft_len, jnp.int32),
                             jnp.asarray(active, bool))

    def lower_decode(self, state: InferenceState) -> jax.stages.Lowered:
        """Lower the fused all-slot decode step for ``state`` (arrays or
        ``jax.ShapeDtypeStruct`` leaves) without running it — the program
        :meth:`decode` runs, for callers that inspect what it compiles to
        (e.g. that paged attention is the Pallas kernel)."""
        if self.paged:
            jfn = self._get_jit("decode_paged", state)
            return self._run(jfn.lower, state,
                             jax.ShapeDtypeStruct((self.slots,), bool))
        return self._run(self._get_jit("decode", state).lower, state)

    def decode(self, state: InferenceState, active=None):
        """One decode step over ALL slots: each slot's last token advances
        its own position counter.  Returns (state, greedy tokens (slots,));
        free slots produce garbage tokens the scheduler ignores.

        In paged mode ``active`` (slots,) bool gates all writes: inactive
        slots neither touch the page pool nor advance their counters.
        Mask-free calls are safe against evicted slots (``release_pages``
        clears their page rows, turning stray writes into dropped
        scatters), but the mask is REQUIRED while any slot is
        mid-chunked-prefill — only the caller knows those slots, and an
        unmasked decode would advance their recurrent state.  In paged
        mode the returned state's ``expert_counts`` are the step's (the
        active rows' pairs)."""
        if self.paged:
            if active is None:
                active = np.ones((self.slots,), bool)
            jfn = self._get_jit("decode_paged", state)
            with profiler.annotate("serve.decode"):
                return self._run(jfn, state, jnp.asarray(active, bool))
        if active is not None:
            raise ValueError("active masks are a paged-mode feature; the "
                             "contiguous decode advances every slot")
        jfn = self._get_jit("decode", state)
        with profiler.annotate("serve.decode"):
            return self._run(jfn, state)
