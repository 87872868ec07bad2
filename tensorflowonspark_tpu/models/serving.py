"""Continuous batching for compiled KV-cache decode.

The reference has no serving stack at all (SURVEY.md §2d stops at a
SavedModel batch-inference utility); this module is part of the rebuild's
beyond-parity inference story, alongside speculative decoding and int8/
int4 quantization (``models/gpt.py``, ``ops/quant.py``).

Static batching wastes the accelerator twice: a new request waits for the
whole running batch to finish, and a finished row keeps occupying its
batch slot until the stragglers drain.  Continuous batching fixes both by
treating the decode batch as ``max_batch`` independent SLOTS over one
static-shape KV cache:

- every slot decodes at its own cache offset
  (``GPTConfig.per_row_positions``: the per-layer ``index`` and
  learned-position ``pos`` counters are ``[B]`` vectors);
- new requests are PREFILLED straight into their leased pages —
  same-bucket arrivals admitted together share ONE batched prefill
  dispatch, which also seats their block tables and counters in free
  slots (running slots never recompile, never stall, and never see
  the new prompts);
- a finished slot is released immediately and can be re-admitted on the
  very next step.

Everything on the hot path is compiled exactly once: ONE decode-step
executable for the whole lifetime (all shapes static; with
``decode_block_steps`` add one scanned K-step executable per
power-of-two block size actually taken — O(log K), each reused for the
lifetime), one prefill
executable per (power-of-two prompt BUCKET, power-of-two admission
GROUP size) pair — prompts are right-padded internally and the pad
positions provably never leak (see ``_prefill``), so
arbitrary-length traffic costs O(log max_len x log max_batch)
compiles, not one per length; with ``prefill_chunk`` long prompts add
one fixed-chunk executable and stream through the cache solo,
TIME-SLICED one chunk per step so running slots keep decoding while a
long admission is in flight, with O(chunk x max_len) transient
attention memory.  A BURST of arrivals therefore costs O(distinct
buckets) device dispatches, not O(requests): the admission regime
continuous batching exists for.  The decode loop itself is plain
Python — admission decisions are host-side control flow, exactly what
should NOT be traced.

The cache substrate is PAGED (vLLM-shaped), and it is the only one:
per-layer K/V pools of ``kv_page_tokens``-token pages behind per-row
block tables (``models/gpt.py``), host-side page accounting with a
refcounted shared-prefix index (``models/kv_pages.py``), admission
tied to free PAGES as well as free slots, and prefix-hit requests
prefilling only their tails — the fused ``_prefill`` executable
prefills, selects first tokens, and scatters block tables + counters
in one dispatch (docs/serving.md "KV paging & prefix cache").  Beside
the pages a slot may own fixed-size rows of RECURRENT state (conv,
retention and Mamba-2 layers, ``models.gpt.STATE_LEAVES``), moved by the
same admission, parking and chunked-prefill code; a model with no attention
layer has only those, and its batcher builds no pool at all.

Output contract (locked by ``tests/test_serving.py``): a request's
tokens are a pure function of its own (params, prompt, budget,
temperature, top_p, seed) — never of admission order, slot reuse, or
what else shares the batch.  ``temperature=0`` (default) is
**greedy-exact**: identical to a solo ``greedy_generate`` run on that
prompt.  ``temperature>0`` samples the nucleus ``top_p`` (shared
``nucleus_filter`` with ``sample_generate``), keyed
``fold_in(key(seed), n)`` for the request's n-th token.
"""

from __future__ import annotations

import dataclasses
import itertools
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from tensorflowonspark_tpu import observability as _obs
from tensorflowonspark_tpu.models import moe as _moe
from tensorflowonspark_tpu.models.gpt import (GPT, GPTConfig,
                                              attends_pages_in_place,
                                              init_cache, is_state_leaf,
                                              nucleus_filter,
                                              state_step_bytes)
from tensorflowonspark_tpu.models.kv_pages import (KVPagePool, NoPages,
                                                   hash_page_data)

#: compile site -> the program's name, by ROLE and never by shape: the
#: profiler reads ``jit_<name>``, and a reduction that selects a program
#: by name must find the same name at every bucket, group and block size
#: (docs/observability.md "Profiler spans").  A new compile site picks its
#: name here.
PROGRAM_NAMES = {
    "step": "tfos_decode", "step_sample": "tfos_decode_sampled",
    "block": "tfos_decode_block", "verify": "tfos_verify",
    "final": "tfos_prefill", "chunk": "tfos_prefill_chunk",
    "padopt": "tfos_kv_seat", "park": "tfos_kv_park",
    "pexport": "tfos_kv_export", "draft_propose": "tfos_draft"}


#: why a decode dispatch had no plain step dispatched ahead behind it
#: (``ContinuousBatcher._stands_down``, in the order it asks): the batcher
#: decides each dispatch from the last one's tokens (``speculative_k``,
#: ``decode_block_steps``); an ``eos_id`` can end a row at any step; a
#: chunked admission is in flight; a seated row is sampled; a row was seated
#: since the step was dispatched (or the step was consumed by an admission,
#: ahead of its prefill's fetch); no seated row goes on; ``settle()`` asked
STANDDOWNS = ("alternative", "eos", "chunked", "sampled", "admission",
              "idle", "settle")


#: the page axis of a paged pool leaf seen as pages (:func:`_as_pages`)
#: and of an exported page array ``[..., n, pt, W]``
_PAGE_AXIS = -3


def _as_pages(leaf, pages: int, page_tokens: int):
    """A paged pool leaf ``[P*pt, W]`` (``[L, P*pt, W]`` under
    ``scan_layers``; ``W`` = ``models.gpt.kv_row_width``) viewed as
    ``[..., P, pt, W]``."""
    return leaf.reshape(leaf.shape[:-2] + (pages, page_tokens,
                                           leaf.shape[-1]))


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _named(site, fn):
    """``fn`` named for its compile site's role (:data:`PROGRAM_NAMES`);
    ``jax.jit`` names the compiled module ``jit_<fn.__name__>``."""
    fn.__name__ = fn.__qualname__ = PROGRAM_NAMES[
        site if isinstance(site, str) else site[0]]
    return fn


@dataclass
class _Slot:
    request_id: int
    remaining: int
    tokens: list = field(default_factory=list)  # generated so far
    temperature: float = 0.0                    # 0 = greedy
    top_p: float = 1.0
    seed: int = 0
    lease: object = None                        # its PageLease
    prompt_len: int = 0     # positions seated by the admission
    parked: bool = False    # its row was parked on the device ahead of its
    #                         last step's fetch (``_plain_step``)


def _apply(model, params, cache, tokens, lengths=None):
    """One cached forward: ``(logits, cache, expert stats)``.  The stats
    are the expert layers' sown counts (``models.moe``: assignments made,
    the busiest held expert's, held experts touched, assignments to held
    experts) as one flat int32 vector, ``STATS_PER_LAYER`` per expert
    layer, or None for a model without experts.  ``lengths``
    (a padded prefill of a model with recurrent state) goes to the model
    only when given, so a dense model's trace is the one it always was."""
    kwargs = {} if lengths is None else {"lengths": lengths}
    logits, vars_ = model.apply(
        {"params": params, "cache": cache}, tokens,
        mutable=["cache", _moe.STATS], **kwargs)
    sown = jax.tree.leaves(vars_.get(_moe.STATS, {}))
    stats = jnp.concatenate(sown) if sown else None
    return logits, vars_["cache"], stats


def _row_view(cache, row_bt, row_start, state_rows=None):
    """The batch's cache as a prefill's rows see it: the shared pools as
    they are, every ``block_table`` the rows' tables ``row_bt [rows,
    pages]``, every counter the rows' start positions, every leaf of
    recurrent state (``models.gpt.STATE_LEAVES``) the rows' carried state
    (``state_rows``: one ``[rows, ...]`` array per leaf, in traversal
    order) or zeros for rows that start fresh (None)."""
    state_in = iter(state_rows or ())

    def rows(path, leaf):
        k = getattr(path[-1], "key", None)
        if is_state_leaf(path):
            return next(state_in) if state_rows is not None else jnp.zeros(
                row_bt.shape[:1] + leaf.shape[1:], leaf.dtype)
        if k == "block_table":
            return jnp.broadcast_to(row_bt, leaf.shape[:-2] + row_bt.shape)
        if k in ("index", "pos"):
            return jnp.broadcast_to(
                row_start, leaf.shape[:-1] + row_start.shape
            ).astype(leaf.dtype)
        return leaf     # the shared pool

    return jax.tree_util.tree_map_with_path(rows, cache)


def _pack(tokens, stats):
    """Tokens and expert stats as ONE int32 vector, so that the host's one
    fetch of the tokens brings the stats (no second synchronisation in a
    loop turn); a model without experts returns its tokens as they are."""
    if stats is None:
        return tokens
    return jnp.concatenate([tokens.reshape(-1).astype(jnp.int32), stats])


def _decode_one_greedy(model, params, cache, tokens):
    """THE greedy decode step — the per-step executables and the
    ``decode_block_steps`` scan bodies both call this, so the
    block==per-step token-exactness contract cannot drift.  Returns
    ``(next tokens, expert stats | None, cache)``."""
    logits, cache, stats = _apply(model, params, cache, tokens[:, None])
    return jnp.argmax(logits[:, -1], axis=-1), stats, cache


def _decode_one_sampled(model, params, cache, tokens, seeds, steps,
                        temps, top_ps):
    """THE sampled decode step (see :func:`_decode_one_greedy`)."""
    logits, cache, stats = _apply(model, params, cache, tokens[:, None])
    nxt = _select_tokens(logits[:, -1], seeds, steps, temps, top_ps)
    return nxt, stats, cache


def _select_tokens(logits, seeds, steps, temps, top_ps):
    """Per-row next-token selection: greedy at temperature 0, else
    nucleus (top-p) sampling at the given temperature.

    Sampling is keyed ``fold_in(key(seed), step)`` where ``step`` is the
    request's OWN generated-token count — so a request's n-th token
    depends only on ``(seed, n)``, never on batch company, slot index, or
    admission order (locked by tests/test_serving.py)."""
    def pick(row, seed, step, temp, top_p):
        key = jax.random.fold_in(jax.random.key(seed), step)
        greedy = jnp.argmax(row)
        scaled = row.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
        sampled = jax.random.categorical(key, nucleus_filter(scaled, top_p))
        return jnp.where(temp <= 0.0, greedy, sampled)

    with jax.named_scope("sample"):
        return jax.vmap(pick)(logits, seeds, steps, temps, top_ps)


class DraftModel:
    """Pluggable draft provider for draft-model speculative decoding
    (Leviathan et al.): a SMALL model whose jitted forward proposes up
    to k greedy tokens per decode row, which the target's fused verify
    dispatch then accepts/rejects (``ContinuousBatcher.set_draft``).

    Cache-less by design: the decode loop is dispatch-bound, not
    compute-bound (``bench_artifacts/sharded_serving.json``), so the
    draft re-runs a full no-KV-cache forward over each row's trailing
    ``window`` tokens inside ONE scanned k-step dispatch instead of
    mirroring the target's paged-cache admission machinery.  The win is
    2 dispatches (propose + verify) per up-to-(k+1) committed tokens;
    the cost is O(k × window) tiny-model positions of redundant
    compute, bounded by ``window`` regardless of context length.

    Correctness never depends on the draft: proposals are only
    committed where the target's own argmax agrees (the ``_verify_jit``
    contract), so an untrained, truncated-context, or plain WRONG draft
    costs acceptance, never exactness.  ``window + k`` must fit the
    draft's ``max_position_embeddings`` (checked at ``set_draft``).

    The batcher propagates its AOT executable cache into an armed
    draft, so propose executables pre-bake/load exactly like the
    target's serve steps.
    """

    def __init__(self, cfg: GPTConfig, params, window: int = 64):
        if window < 1:
            raise ValueError(f"draft window must be >= 1, got {window}")
        self.cfg = cfg
        self.params = params
        self.window = int(window)
        self.model = GPT(cfg)          # full forward — no decode cache
        self.dispatches = 0
        self._aot = None               # set by ContinuousBatcher.set_draft
        self._jits: dict = {}
        self._spans = _obs.PhaseSpans()

    def _propose_jit(self, B: int, L: int, k: int):
        key = (B, L, k)
        if key in self._jits:
            return self._jits[key]
        model = self.model
        rows = jnp.arange(B)

        def propose_fn(params, buf, lens):
            def body(carry, _):
                buf, lens = carry
                logits = model.apply({"params": params}, buf)  # [B, L, V]
                nxt = jnp.take_along_axis(
                    jnp.argmax(logits, axis=-1), (lens - 1)[:, None],
                    axis=1)[:, 0]
                buf = buf.at[rows, lens].set(nxt, mode="drop")
                return (buf, lens + 1), nxt

            (_, _), seq = jax.lax.scan(body, (buf, lens), None, length=k)
            return seq.swapaxes(0, 1)                          # [B, k]

        _named("draft_propose", propose_fn)
        if self._aot is None:
            fn = jax.jit(propose_fn)
        else:
            fn = self._aot.wrap(
                ("draft_propose", repr((self.cfg, self.window)), key),
                propose_fn)
        self._jits[key] = fn
        return fn

    def propose(self, buf: np.ndarray, lens: np.ndarray,
                k: int) -> np.ndarray:
        """k greedy draft tokens per row: ``buf [B, window + k]`` holds
        each row's right-zero-padded trailing history, ``lens [B]`` its
        true length (>= 1).  One device dispatch for the whole batch;
        rows the caller deems ineligible simply have their proposals
        ignored (the verify mask ``d`` is what gates commitment)."""
        B, L = buf.shape
        self.dispatches += 1
        with self._spans(_obs.BATCHER_DECODE_DISPATCH):
            seq = self._propose_jit(B, L, int(k))(
                self.params, jnp.asarray(buf), jnp.asarray(lens))
        with self._spans(_obs.BATCHER_DECODE_FETCH):
            return np.asarray(seq)


class ContinuousBatcher:
    """Admit/step/retire decode requests over one compiled batch —
    greedy by default, per-request nucleus sampling via ``submit``'s
    ``temperature``/``top_p``/``seed`` (deterministic per request,
    independent of batch company).

    Usage::

        b = ContinuousBatcher(cfg, params, max_batch=8, eos_id=50256)
        for prompt, n in requests: b.submit(prompt, n)
        results = b.run()          # {request_id: np.ndarray tokens}

    or drive it manually: ``submit`` while ``b.has_free_slot()`` (it
    counts queued-but-unadmitted requests against the free slots),
    ``step()`` once per decode step (returns every request id finished
    since the last call, including ones that completed at admission),
    submit more as slots free up.  Between two ``step()`` calls the
    device may already hold the next step (:meth:`settle`).

    ``decode_ahead`` is accepted and unread: the batcher runs ahead by
    rule, wherever the next step's rows are decided (:meth:`_stands_down`).
    """

    def __init__(self, cfg: GPTConfig, params, max_batch: int,
                 eos_id: int | None = None,
                 prefill_chunk: int | None = None,
                 speculative_k: int | None = None,
                 speculative_ngram: int = 3,
                 speculative_window: int = 2048,
                 decode_block_steps: int | None = None,
                 kv_page_tokens: int | None = None,
                 kv_pool_pages: int | None = None,
                 prefix_cache: bool | None = None,
                 prefill_only: bool = False,
                 prefill_rows_max: int | None = None,
                 decode_ahead: bool | None = None,
                 aot_cache=None):
        if cfg.has_state:
            # what per-row recurrent state cannot follow yet refuses
            # here, loudly, naming the state that caused it
            why = None
            if speculative_k is not None:
                why = ("speculative_k rewinds the cache after each verify "
                       "dispatch")
            elif prefix_cache:
                why = ("prefix_cache=True lets a request start from another "
                       "request's shared pages, which hold no recurrent "
                       "state at their end — pass prefix_cache=False")
            elif prefill_only:
                why = ("prefill_only exports K/V pages as the whole of a "
                       "session")
            if why is not None:
                raise ValueError(
                    f"ContinuousBatcher: {why}; this configuration keeps "
                    f"{cfg.cache_kinds}")
        if prefill_rows_max is not None and (
                prefill_rows_max < 1
                or prefill_rows_max & (prefill_rows_max - 1)):
            raise ValueError(f"prefill_rows_max must be a positive power "
                             f"of two, got {prefill_rows_max}")
        if cfg.rolling_kv_cache:
            raise ValueError("ContinuousBatcher requires a full-length "
                             "cache (rolling_kv_cache=False)")
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        if speculative_k is not None and speculative_k < 1:
            raise ValueError(f"speculative_k must be >= 1, "
                             f"got {speculative_k}")
        if speculative_ngram < 1:
            raise ValueError(f"speculative_ngram must be >= 1, "
                             f"got {speculative_ngram}")
        if speculative_window < speculative_ngram + 1:
            raise ValueError(f"speculative_window must be > "
                             f"speculative_ngram, got {speculative_window}")
        if decode_block_steps is not None and decode_block_steps < 2:
            raise ValueError(f"decode_block_steps must be >= 2, "
                             f"got {decode_block_steps}")
        if decode_block_steps is not None and speculative_k is not None:
            # drafting is host-side control flow per step; it cannot run
            # inside a scanned block — the two amortization strategies
            # are alternatives, not composable
            raise ValueError(
                "decode_block_steps and speculative_k are mutually "
                "exclusive (a scanned block cannot host the per-step "
                "draft/verify control flow) — for multi-token decode "
                "dispatches keep speculative_k and arm a draft model "
                "(set_draft / ServingCluster.run(draft_model=)) instead "
                "of blocking")
        if prefill_only and (speculative_k is not None
                             or decode_block_steps is not None):
            raise ValueError("prefill_only is a prefill-pool posture; "
                             "speculative_k/decode_block_steps are "
                             "decode-time knobs")
        #: multi-step decode: when no admission work is pending, run up
        #: to this many decode steps inside ONE ``lax.scan`` dispatch
        #: (power-of-two block sizes -> O(log block) compiles).  The
        #: host sees identical tokens — the scan body is the plain step
        #: — but pays one dispatch per BLOCK instead of per token: the
        #: lever for deployments where dispatch latency rivals step time
        #: (remote dispatch; even local PJRT costs ~0.1 ms
        #: against the ~2 ms steps of small-model decode)
        self.decode_block_steps = decode_block_steps
        #: prompt-lookup speculative decoding INSIDE continuous batching:
        #: every decode step drafts up to ``speculative_k`` tokens per
        #: greedy slot from that request's own history (the most recent
        #: ``speculative_ngram`` context match — no draft model) and one
        #: fused verify dispatch processes ``k+1`` positions for all
        #: slots.  Unlike ``lookup_generate``'s shared cache index (whose
        #: batch advances by the MINIMUM acceptance), the per-row position
        #: substrate lets every slot commit ITS OWN accepted length.
        #: Greedy-exact: drafts are only accepted where they equal the
        #: model's own argmax; sampled slots simply draft 0 and take the
        #: usual nucleus sample from the boundary logits.
        self.spec_k = speculative_k
        self.spec_ngram = speculative_ngram
        #: drafting scans only the trailing ``speculative_window`` tokens
        #: of a request's history, so per-step host cost is O(window),
        #: not O(history) — a 100k-token context must not make the decode
        #: loop host-bound (recent context is also where lookup hits live)
        self.spec_window = speculative_window
        #: speculation accounting: tokens proposed/accepted and committed
        #: per verify dispatch (tokens_per_dispatch > 1 is the win)
        self.spec_proposed = 0
        self.spec_accepted = 0
        #: draft-MODEL speculation (:meth:`set_draft`): when armed, a
        #: jitted small-model forward proposes the k tokens instead of
        #: the prompt-lookup n-gram match — same verify, same
        #: greedy-exact acceptance, but proposals exist for novel text
        #: too.  None = prompt-lookup drafting (the historical default).
        self._draft_model = None
        #: draft-model propose dispatches (each covers every eligible
        #: row; compare spec_accepted for the tokens-per-dispatch story)
        self.draft_dispatches = 0
        #: per-row accepted draft lengths, one entry per drafted row per
        #: verify dispatch — drained by :meth:`take_spec_accept_lens`
        #: into the replica's ``tfos_replica_spec_accept_len`` histogram
        self._accept_lens: list[int] = []
        #: long-context admission: prompts longer than this are prefilled
        #: in fixed-size chunks through the SAME cached decode path (the
        #: cache index advances per chunk), bounding the transient
        #: attention-score memory at O(chunk x max_len) instead of
        #: O(prompt x max_len) — the chunk loop adds executables only for
        #: (one fixed chunk length + the bucketed final chunk)
        self.prefill_chunk = prefill_chunk
        #: the most rows ONE prefill dispatch takes (a power of two; None
        #: = a whole admission group): a burst's group is cut into
        #: dispatches of at most this many rows, because a prefill's
        #: temporaries (the scores ``[rows, heads, bucket, max_len]``
        #: float32 first) grow with its rows and the chip's memory that
        #: the weights leave free does not
        self.prefill_rows_max = prefill_rows_max
        #: RUN-AHEAD, by rule (:meth:`_stands_down`): while every seated row
        #: is greedy and was in the running step, no ``eos_id`` can end a
        #: row early and one row at least goes on, the next plain decode
        #: step's rows are already known, so it is dispatched BEFORE the
        #: running step's tokens are fetched, fed the running step's tokens
        #: as they lie on the device.  A row at its last token ends at the
        #: running step BY BUDGET: its parking is dispatched between the two
        #: steps, so the next step meets it parked.  A free slot stops
        #: nothing: a step runs every row, seated or parked.  The device then
        #: runs step after step with no host turn between them, and a late
        #: wake-up of the host shorter than a step costs nothing.
        #: Token-exact (the same executable, the same inputs).  A request
        #: admitted while a step is queued is prefilled BEHIND it and joins
        #: the decode at the next dispatch (:meth:`_prefill`), which
        #: ``step()`` makes before it returns, from the host's tokens, as
        #: soon as the new row's first one is there (``_step_inner``).
        #: ``_ahead`` is the step dispatched and not yet consumed: its
        #: packed tokens on the device, and the slots' rows it holds (None
        #: where a slot was free or parked).  While it is set ``self.cache``
        #: is one step ahead of the slots (:meth:`settle` makes them agree).
        #: ``decode_ahead_dispatches`` counts the steps dispatched behind a
        #: running one, at most one per decode dispatch —
        #: ``tfos_replica_decode_ahead_dispatches_total``;
        #: ``decode_ahead_standdowns[why]`` counts the plain steps that had
        #: none dispatched behind them (:data:`STANDDOWNS`), so the two sum
        #: to ``decode_dispatches`` —
        #: ``tfos_replica_decode_ahead_standdowns_total{why}``
        self._ahead: tuple | None = None
        self.decode_ahead_dispatches = 0
        self.decode_ahead_standdowns = dict.fromkeys(STANDDOWNS, 0)
        #: what the queued step finished, where an admission consumed it
        #: ahead of its own fetch (:meth:`_prefill`), for ``step()`` to return
        self._settled: list[int] | None = None
        #: THE K/V STORE: a pool of ``kv_pool_pages`` pages of
        #: ``kv_page_tokens`` tokens (a power of two) behind per-row block
        #: tables (``models/gpt`` device side, ``models/kv_pages``
        #: host-side accounting), with admission tied to FREE PAGES as
        #: well as free slots and — unless ``prefix_cache=False`` — a
        #: refcounted shared-prefix index so a request whose prompt starts
        #: like a cached one skips straight to prefilling the tail.
        #: Token-exact vs the generators' dense cache on hit and miss
        #: paths alike (the locked greedy oracle covers both).
        #: A configuration with NO ``full_attention`` layer owns no K/V:
        #: no pool, no block table (``_table_pages`` 0), a page
        #: accountant with nothing to count (``kv_pages.NoPages``), and
        #: admission bounded by free slots alone.
        if not cfg.num_attention_layers:
            self.cfg = dataclasses.replace(cfg, per_row_positions=True)
            self._pages = NoPages()
            self._table_pages = 0
        else:
            if kv_page_tokens is None:
                # the page every cell serves from, halved until it divides
                # the window so that a toy configuration still builds
                pt = 16
                while cfg.max_position_embeddings % pt:
                    pt //= 2
            else:
                pt = int(kv_page_tokens)
            per_req = -(-cfg.max_position_embeddings // pt)
            # default pool: every slot can hold a max-length request;
            # smaller pools are legal — the memory lever — and ``submit``
            # rejects any single request the whole pool cannot hold, so
            # admission stays live
            pool_pages = (int(kv_pool_pages) if kv_pool_pages is not None
                          else int(max_batch) * per_req)
            # dataclass validation (pow2, divisibility, int8/rolling
            # conflicts) happens in GPTConfig.__post_init__
            self.cfg = dataclasses.replace(
                cfg, per_row_positions=True, kv_page_tokens=pt,
                kv_pool_pages=pool_pages)
            # pages shared from another request's prompt hold no recurrent
            # state at their end, so a configuration with such layers has
            # no index
            self._pages = KVPagePool(
                pool_pages, pt,
                prefix_cache=(not cfg.has_state if prefix_cache is None
                              else bool(prefix_cache)))
            #: entries of one row's block table
            self._table_pages = per_req
        self.params = params
        #: the compiled executables are keyed on this tree's structure +
        #: leaf shapes/dtypes; load_params validates every later tree
        #: against it (a hot-swapped or cloned version with a different
        #: architecture must bounce, not silently crash a dispatch)
        self._params_struct = self._struct_of(params)
        self.max_batch = int(max_batch)
        self.eos_id = eos_id
        self.model = GPT(self.cfg, decode=True)
        self.cache = init_cache(self.cfg, params, self.max_batch)
        self.slots: list[_Slot | None] = [None] * self.max_batch
        #: PREFILL-ONLY mode (disaggregated serving's prefill-pool
        #: posture, docs/serving.md "Disaggregated prefill/decode"): the
        #: batcher admits and prefills exactly as usual — shared prefix
        #: index, chunked streaming, batched bucket dispatches — but a
        #: seated request never decode-steps.  Instead its session
        #: (prompt KV pages + first token + sampler state) is EXPORTED
        #: for :meth:`take_sessions` to drain, and its pages release
        #: immediately (full prompt pages stay in the prefix index, so
        #: repeat system prompts keep amortizing).  The receiving decode
        #: pool seats such a session via :meth:`adopt_session` without
        #: re-prefilling a single token.
        self.prefill_only = bool(prefill_only)
        #: (request_id, session) pairs exported since the last
        #: :meth:`take_sessions` drain (prefill-only mode)
        self._sessions: list[tuple[int, dict]] = []
        #: (request_id, session) adoptions awaiting a slot + pages
        self._pending_adopt: list[tuple[int, dict]] = []
        #: lifetime handoff counters: sessions this batcher exported
        #: (prefill pool) / seated via :meth:`adopt_session` (decode
        #: pool) — the bench's "prefill never ran on a decode gang"
        #: accounting reads these, not ``prefill_dispatches``
        self.sessions_exported = 0
        self.sessions_adopted = 0
        #: lifetime dispatch counters — ``prefill_dispatches`` (a batched
        #: group admission counts ONCE; chunk-loop calls excluded) and
        #: ``decode_dispatches`` (one per decode DISPATCH with active
        #: slots — a ``decode_block_steps`` block counts once here while
        #: covering up to K steps; use ``decode_steps`` for step counts).
        #: Public so benches/demos read them instead of patching
        #: private methods.
        self.prefill_dispatches = 0
        self.decode_dispatches = 0
        #: decode STEPS executed (== dispatches without blocking; with
        #: ``decode_block_steps`` each block dispatch counts its scanned
        #: steps here) — steps/dispatches is the amortization ratio
        self.decode_steps = 0
        #: expert-layer accounting (``GPTConfig.num_experts``), summed over
        #: expert layers and over every decode and prefill dispatch whose
        #: tokens the host fetched (the stats ride that fetch: ``_pack``;
        #: a chunk slice of ``prefill_chunk`` has no fetch and is not
        #: counted): assignments made (rows x experts per token), and of
        #: the experts this chip holds (``GPTConfig.experts_held``: all of
        #: them unless told) the busiest one's assignments, those that got
        #: at least one, and the assignments that fell to them —
        #: ``tfos_replica_expert_assignments_total``,
        #: ``..._expert_peak_assignments_total``,
        #: ``..._experts_touched_total``,
        #: ``..._expert_assignments_held_total``; and the part of the
        #: experts touched that prefill dispatches account for (the rest
        #: is the decode steps') —
        #: ``tfos_replica_prefill_experts_touched_total``
        self.expert_assignments = 0
        self.expert_peak_assignments = 0
        self.experts_touched = 0
        self.expert_assignments_held = 0
        self.prefill_experts_touched = 0
        #: rows whose recurrent state an admission wrote (configurations
        #: with conv or retention layers; 0 otherwise) —
        #: ``tfos_replica_state_rows_seated_total``
        self.state_rows_seated = 0
        #: prefill dispatches that brought recurrent state with them (a
        #: chunked admission's final call): over ``prefill_dispatches``,
        #: the share whose retention layers had a state to query —
        #: ``tfos_replica_carried_prefills_total``
        self.carried_prefills = 0
        #: bytes of per-row recurrent state the decode steps read and
        #: wrote: host arithmetic, ``models.gpt.state_step_bytes`` of the
        #: whole batch (a step runs every row, seated or parked) per step
        #: — ``tfos_replica_state_bytes_moved_total``
        self.state_bytes_moved = 0
        self._state_step_bytes = state_step_bytes(self.cfg, self.max_batch)
        #: ``tfos_grouped_matmul`` kernel calls the dispatched decode and
        #: prefill programs hold (``models.moe.grouped_matmul_calls`` per
        #: step: the model's own rule, read once, in the scope the batcher
        #: is built and stepped in; 0 = the ``ragged_dot`` path ran, or the
        #: model has no expert layer) —
        #: ``tfos_replica_grouped_matmul_calls_total``
        self.grouped_matmul_calls = 0
        self._step_grouped_matmul_calls = _moe.grouped_matmul_calls(self.cfg)
        #: per decode dispatch and summed over the seated rows:
        #: the pages a row's length covers (``kv_pages_read``) and, only
        #: when the step attends over the pages in place
        #: (``models.gpt.attends_pages_in_place``), the pages of its whole
        #: view (``kv_pages_viewed``).  read / viewed is the share of the
        #: view the kernel touches; viewed == 0 says the gather path ran —
        #: ``tfos_replica_kv_pages_read_total``, ``..._viewed_total``
        self.kv_pages_read = 0
        self.kv_pages_viewed = 0
        #: whether this batcher's decode step attends in place: the model's
        #: own rule, read once, in the scope the batcher is built and
        #: stepped in
        self._attends_in_place = attends_pages_in_place(self.cfg)
        #: set to the original error message the first time a device step
        #: raises mid-flight; every executable donates the cache buffer
        #: (``donate_argnums``), so after a failed dispatch the previous
        #: cache is already consumed and slot/device state can no longer
        #: be trusted — the instance refuses further use instead of
        #: silently decoding from a poisoned cache
        self._poisoned: str | None = None
        # (rid, prompt, budget, temperature, top_p, seed)
        self._pending: list[tuple[int, np.ndarray, int,
                                  float, float, int]] = []
        #: the at-most-one chunked admission in flight: its prefill is
        #: TIME-SLICED — one chunk per ``step()`` — so admitting a long
        #: prompt never stalls running slots for the whole chunk loop;
        #: the target slot is reserved until the final chunk scatters
        self._inflight: dict | None = None
        self._reserved: set[int] = set()
        self._ids = itertools.count()
        self._results: dict[int, np.ndarray] = {}
        #: per-request streaming callbacks (``submit(on_token=...)``);
        #: dropped at finish alongside the request's other live state
        self._on_token: dict[int, object] = {}
        #: prompt per live request (speculative drafting needs the full
        #: history); dropped at finish so memory tracks the in-flight set
        self._prompts: dict[int, np.ndarray] = {}
        # compiled-program registry, keyed by site (PROGRAM_NAMES) and
        # shape: ("final", pow2_bucket, pow2_rows) -> batched prefill jit,
        # ("chunk", chunk_len) -> chunk jit, ...
        self._prefill_jit: dict = {}
        #: optional :class:`~tensorflowonspark_tpu.serving.aot.
        #: AOTExecutableCache`: every compile site below routes through
        #: :meth:`_jit`, so an armed batcher resolves its serve-step
        #: executables as serialized-artifact LOADS (compile-and-store
        #: on miss) — the standby warm-up / cold-replica lever.  The
        #: context string disambiguates entries across models/knobs
        #: sharing one cache directory.
        self._aot = aot_cache
        #: the loop thread's phase spans (docs/observability.md "Profiler
        #: spans"): admit, prefill/decode dispatch and fetch, emit
        self._spans = _obs.PhaseSpans()
        self._aot_ctx = None if aot_cache is None else repr(
            (self.cfg, self.max_batch, self.spec_k, self.spec_ngram,
             self.prefill_chunk, self.decode_block_steps))

        n_stats = _moe.STATS_PER_LAYER * self.cfg.num_expert_layers

        def step_greedy(params, cache, tokens):
            if n_stats:
                # a model with experts takes its tokens in the shape it
                # returns them (:func:`_pack`), so that a step dispatched
                # ahead is fed the last step's output as it is; a dense
                # model's program is the one it always was
                tokens = tokens[:self.max_batch]
            nxt, stats, cache = _decode_one_greedy(self.model, params,
                                                   cache, tokens)
            return _pack(nxt, stats), cache

        def step_sample(params, cache, tokens, seeds, steps, temps, top_ps):
            nxt, stats, cache = _decode_one_sampled(
                self.model, params, cache, tokens, seeds, steps, temps,
                top_ps)
            return _pack(nxt, stats), cache

        # two executables so all-greedy traffic (the common batch) never
        # pays the per-row sort/sample computation
        self._step = self._jit(("step",), step_greedy, donate_argnums=(1,))
        self._step_sample = self._jit(("step_sample",), step_sample,
                                      donate_argnums=(1,))

    def _jit(self, site, fn, donate_argnums=()):
        """THE compile-site chokepoint: plain ``jax.jit`` without an AOT
        cache, else the cache's load-or-compile wrapper keyed on (site,
        this batcher's config context, arg avals).  Both are lazy and
        call-compatible, so the executable registry stores either.  The
        program is named for the site's role (:data:`PROGRAM_NAMES`); the
        AOT cache keys on ``site``, never on that name."""
        _named(site, fn)
        if self._aot is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        return self._aot.wrap((site, self._aot_ctx), fn,
                              donate_argnums=donate_argnums)

    def aot_stats(self) -> dict | None:
        """The AOT executable cache's ``{dir, loads, compiles, errors}``
        counters, or None for an uncached batcher — benches and
        ``scripts/tfos_warmcache.py`` gate on ``compiles == 0`` for a
        fully pre-baked warm-up."""
        return None if self._aot is None else self._aot.stats()

    def _fetch(self, packed, shape=None, prefill=False) -> np.ndarray:
        """The host's fetch of a dispatch's tokens (the caller holds the
        fetch span): splits off the expert stats that rode with them
        (:func:`_pack`) into the lifetime counters, a ``prefill``
        dispatch's experts touched into a counter of their own too."""
        out = np.asarray(packed)
        n = _moe.STATS_PER_LAYER * self.cfg.num_expert_layers
        if n:
            made, peak, touched, held = out[-n:].reshape(
                -1, _moe.STATS_PER_LAYER).sum(axis=0)
            self.expert_assignments += int(made)
            self.expert_peak_assignments += int(peak)
            self.experts_touched += int(touched)
            self.expert_assignments_held += int(held)
            if prefill:
                self.prefill_experts_touched += int(touched)
            out = out[:-n]
        return out if shape is None else out.reshape(shape)

    def _check_usable(self) -> None:
        if self._poisoned is not None:
            raise RuntimeError(
                "ContinuousBatcher is unusable: a device step failed "
                "after its KV cache was donated, so in-flight requests "
                "and the cache are unrecoverable. Build a new batcher "
                f"and resubmit. Original error: {self._poisoned}")
        if self.params is None:
            raise RuntimeError(
                "ContinuousBatcher has no parameters loaded "
                "(unload_params() — warm-standby mode); call "
                "load_params() before submitting")

    # -- warm-standby parameter swap --------------------------------------
    def unload_params(self) -> None:
        """Drop the parameter tree while KEEPING every compiled
        executable (the jitted step/prefill registry is keyed on shapes,
        not values) — the warm-standby posture: a batcher that has paid
        its compiles but holds no weights.  Refuses while any request is
        live; ``submit`` raises until :meth:`load_params` re-arms it."""
        if self.load()["total"] or self._reserved:
            raise RuntimeError(
                "cannot unload params with live requests "
                f"(load={self.load()})")
        self.params = None

    @staticmethod
    def _struct_of(params) -> tuple:
        """``(treedef, [(shape, dtype)])`` signature of a parameter
        tree — what the compiled executables are keyed on."""
        leaves, treedef = jax.tree_util.tree_flatten(params)
        return (treedef,
                [(tuple(np.shape(x)), str(getattr(x, "dtype", "?")))
                 for x in leaves])

    def load_params(self, params) -> None:
        """(Re)arm the batcher with a parameter tree of the SAME
        structure/shapes it compiled against — a peer-cloned,
        checkpoint-restored, or hot-swapped model version.  The
        compiled dispatches are reused as-is, so the cost is the weight
        transfer, not a recompile; a tree whose structure or leaf
        shapes/dtypes differ from the compiled ones raises
        ``ValueError`` (the multi-model hot-swap path turns this into a
        typed ``model_swap_failed`` instead of a poisoned dispatch).
        The pool's PREFIX INDEX is rebuilt empty — cached pages hold
        KV computed under the OLD weights, and a post-swap prefix hit
        against them would silently decode wrong tokens when the new
        tree differs (e.g. a later-checkpoint restore)."""
        if params is None:
            raise ValueError("load_params needs a parameter tree")
        treedef, leaves = self._struct_of(params)
        want_def, want_leaves = self._params_struct
        if treedef != want_def:
            raise ValueError(
                "load_params: parameter tree structure differs from the "
                "one this batcher compiled against (another "
                "architecture/version?) — rebuild the batcher instead")
        bad = [i for i, (got, want) in enumerate(zip(leaves, want_leaves))
               if got != want]
        if bad:
            raise ValueError(
                f"load_params: {len(bad)} leaf(s) differ in shape/dtype "
                f"from the compiled tree (first: leaf {bad[0]} got "
                f"{leaves[bad[0]]}, want {want_leaves[bad[0]]}) — an "
                "incompatible model version cannot reuse these "
                "executables")
        # idle by the unload_params contract: every page is free or
        # parked in the (now-stale) prefix cache — a fresh pool of the
        # same geometry drops the index without touching the device-side
        # tables (idle rows are parked at the sentinel)
        self._pages = self._pages.fresh()
        self.params = params

    def set_role(self, role: str | None) -> None:
        """Specialize an idle batcher for a disaggregated pool role —
        the promote-with-role path of a warm standby joining a
        prefill/decode tier (a standby's engine is built role-less so
        ONE pool can back both specializations).  ``"prefill"`` flips
        :attr:`prefill_only` on, under the same constraints the
        constructor enforces (no decode-time amortization knobs);
        ``"decode"``/``None`` flips it off (adoption readiness is
        checked by ``adopt_session`` itself).  Only legal while no
        request is live: a seated request's posture must never change
        under it."""
        if role not in (None, "prefill", "decode"):
            raise ValueError(f"unknown role {role!r} "
                             "(want 'prefill', 'decode' or None)")
        if self.load()["total"] or self._reserved:
            raise RuntimeError(
                f"cannot set_role({role!r}) with live requests "
                f"(load={self.load()})")
        if role == "prefill":
            if self.cfg.has_state:
                raise ValueError(
                    "prefill role exports K/V pages as the whole of a "
                    "session; this configuration keeps "
                    f"{self.cfg.cache_kinds}")
            if self.spec_k is not None or self.decode_block_steps is not None:
                raise ValueError(
                    "prefill role conflicts with speculative_k/"
                    "decode_block_steps (decode-time knobs)")
        self.prefill_only = role == "prefill"

    # -- draft-model speculation ------------------------------------------
    def set_draft(self, draft: "DraftModel | None") -> None:
        """Arm (or clear, with ``None``) a :class:`DraftModel` as the
        speculation proposer: eligible greedy rows get their k draft
        tokens from ONE jitted draft forward instead of the host-side
        prompt-lookup, and the existing fused verify commits the
        agreeing prefix — same oracle, same counters, more accepted
        tokens on workloads n-gram lookup can't predict.  Sampled rows
        keep the draft-0 fallback (their token still comes from the
        verify dispatch's own boundary logits).  Misconfiguration is
        rejected here, up front and typed, not as a mid-serve shape
        blowup.  Swappable while requests are live: correctness never
        depends on WHICH draft proposed (hot-swap coherence)."""
        if draft is None:
            self._draft_model = None
            return
        if not isinstance(draft, DraftModel):
            raise TypeError(
                f"set_draft wants a DraftModel, got {type(draft).__name__}")
        if self.cfg.has_state:
            raise ValueError(
                "draft_model speculation rewinds the target's cache after "
                "each verify dispatch; this configuration keeps "
                f"{self.cfg.cache_kinds}")
        if self.prefill_only:
            raise ValueError(
                "draft_model conflicts with prefill_only: a prefill pool "
                "never decodes, so it has no speculation to accelerate")
        if self.spec_k is None:
            raise ValueError(
                "draft_model needs speculative_k: the draft proposes into "
                "the k-token verify window (pass speculative_k= to the "
                "batcher, or serve_draft_k through the serving tier); "
                f"this configuration keeps {self.cfg.cache_kinds}")
        if draft.cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft/target vocab mismatch: draft vocab_size="
                f"{draft.cfg.vocab_size} vs target "
                f"{self.cfg.vocab_size} — draft proposals index the "
                "target's token space, so the tokenizers must be "
                "identical")
        if draft.window + self.spec_k > draft.cfg.max_position_embeddings:
            raise ValueError(
                f"draft window {draft.window} + speculative_k "
                f"{self.spec_k} exceeds the draft's "
                f"max_position_embeddings "
                f"({draft.cfg.max_position_embeddings}) — shrink the "
                "window (serve_draft_window) or use a longer-context "
                "draft")
        if self._aot is not None and draft._aot is None:
            # the draft's propose executables pre-bake/load through the
            # same AOT cache as the target's serve steps
            draft._aot = self._aot
        self._draft_model = draft

    def take_spec_accept_lens(self) -> list[int]:
        """Drain the per-row accepted-draft-length samples recorded by
        speculative verify dispatches since the last drain — the
        ``tfos_replica_spec_accept_len`` histogram feed (host-side ints,
        one per drafted row per dispatch)."""
        out, self._accept_lens = self._accept_lens, []
        return out

    def _emit_token(self, rid: int, tok: int) -> None:
        cb = self._on_token.get(rid)
        if cb is not None:
            cb(rid, tok)

    def load(self) -> dict:
        """Queue-depth snapshot for routers/schedulers: ``active`` slots
        decoding, ``pending`` queued-but-unadmitted requests (counting the
        at-most-one chunked admission in flight), ``reserved`` slots held
        for that admission, and ``total`` = active + pending — every live
        request counted exactly once.  ``has_free_slot()`` answers "may I
        submit"; this answers "how deep is the queue", which is what
        least-loaded routing across replicas needs.

        ``free_pages``/``total_pages`` surface KV memory pressure: free
        counts allocatable pages RIGHT NOW (free + evictable cached
        prefix pages) — the signal ``serve_replica`` forwards so the
        scheduler's least-outstanding routing can tie-break away from
        memory-starved replicas."""
        active = sum(s is not None for s in self.slots)
        pending = len(self._pending) + len(self._pending_adopt) \
            + (1 if self._inflight is not None else 0)
        return {"active": active, "pending": pending,
                "reserved": len(self._reserved), "total": active + pending,
                "free_pages": self._pages.free_pages(),
                "total_pages": self._pages.total_pages}

    def prefix_stats(self) -> dict:
        """Prefix-cache admission outcomes:
        ``hit`` = every shareable prompt page was already cached,
        ``partial`` = some were, ``miss`` = none; plus ``evictions`` and
        the page-capacity gauges — the source for the replica-side
        ``tfos_replica_prefix_cache_requests_total`` metrics."""
        return self._pages.stats()

    # -- KV-page session handoff (docs/serving.md "Disaggregated
    # prefill/decode"): a prefill-only batcher EXPORTS each admitted
    # request as a session — its prompt KV pages (host numpy, hashed per
    # page), first token, and sampler state — and a decode-pool batcher
    # ADOPTS it into a slot without re-running a single prompt token.
    def _kv_struct(self) -> list:
        """Per-page layout signature of this batcher's pool leaves:
        ``(shape-with-page-axis-removed, dtype)`` per K/V leaf, in cache
        traversal order.  Exported with every transfer and compared on
        import, so a raced handoff from an incompatible replica (other
        model dims, other dtype) is rejected before any device write."""
        pt = self.cfg.kv_page_tokens
        out = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            if getattr(path[-1], "key", None) in ("k", "v"):
                out.append((tuple(int(d) for d in leaf.shape[:-2]
                                  + (pt, leaf.shape[-1])),
                            str(leaf.dtype)))
        return out

    def _gather_pages(self, page_ids: list[int]) -> list[np.ndarray]:
        """Host numpy copies of the pool pages ``page_ids`` from every
        K/V leaf — ONE compiled gather per power-of-two page count (the
        cache is read, never donated: a concurrent prefix-cache clone
        must not invalidate the serving cache)."""
        n = len(page_ids)
        if n == 0:
            return []
        P = self.cfg.kv_pool_pages
        pt = self.cfg.kv_page_tokens
        npad = _next_pow2(n)
        key = ("pexport", npad)
        if key not in self._prefill_jit:
            def export_fn(cache, ids):
                out = []

                def walk(path, leaf):
                    if getattr(path[-1], "key", None) in ("k", "v"):
                        out.append(jnp.take(_as_pages(leaf, P, pt), ids,
                                            axis=_PAGE_AXIS))
                    return leaf

                jax.tree_util.tree_map_with_path(walk, cache)
                return out

            self._prefill_jit[key] = self._jit(key, export_fn)
        ids = np.zeros((npad,), np.int32)
        ids[:n] = page_ids
        got = self._prefill_jit[key](self.cache, jnp.asarray(ids))
        out = []
        for a in got:
            a = np.asarray(a)
            if npad != n:   # drop the pad pages (they gathered page 0)
                a = np.take(a, range(n), axis=_PAGE_AXIS)
            out.append(a)
        return out

    def _seat_pages_device(self, slot: int, row_pages: list[int],
                           import_ids: list[int],
                           kv_sel: list[np.ndarray], counter: int) -> None:
        """ONE fused dispatch that (1) scatters imported page data into
        the K/V pools at ``import_ids`` and (2) seats ``slot``'s block-
        table row (``row_pages``) and cache counters (``counter``).
        ``slot == max_batch`` drops the seat (pure page import — the
        standby prefix-cache clone path); sentinel page ids drop their
        writes.  Compiled once per power-of-two import count."""
        P = self.cfg.kv_pool_pages
        pt = self.cfg.kv_page_tokens
        npg = self.cfg.max_position_embeddings // pt
        n = len(import_ids)
        npad = _next_pow2(max(1, n))
        key = ("padopt", npad)
        if key not in self._prefill_jit:
            def seat_fn(cache, ids, kv, slot_i, row_bt, true_tot):
                it = iter(kv)

                def put(path, leaf):
                    k = getattr(path[-1], "key", None)
                    if k in ("k", "v"):
                        m = jnp.moveaxis(_as_pages(leaf, P, pt),
                                         _PAGE_AXIS, 0)
                        blk = jnp.moveaxis(next(it).astype(leaf.dtype),
                                           _PAGE_AXIS, 0)
                        m = m.at[ids].set(blk, mode="drop")
                        return jnp.moveaxis(m, 0, _PAGE_AXIS).reshape(
                            leaf.shape)
                    if k == "block_table":
                        m = jnp.moveaxis(leaf, -2, 0)
                        v = jnp.broadcast_to(row_bt,
                                             m.shape[1:]).astype(m.dtype)
                        return jnp.moveaxis(
                            m.at[slot_i].set(v, mode="drop"), 0, -2)
                    if k in ("index", "pos"):
                        m = jnp.moveaxis(leaf, -1, 0)
                        v = jnp.broadcast_to(true_tot,
                                             m.shape[1:]).astype(m.dtype)
                        return jnp.moveaxis(
                            m.at[slot_i].set(v, mode="drop"), 0, -1)
                    return leaf

                return jax.tree_util.tree_map_with_path(put, cache)

            self._prefill_jit[key] = self._jit(key, seat_fn,
                                               donate_argnums=(0,))
        ids = np.full((npad,), P, np.int32)   # sentinel pads drop
        ids[:n] = import_ids
        kv_pad = []
        for i, (shape, dt) in enumerate(self._kv_struct()):
            buf = np.zeros(shape[:-2] + (npad,) + shape[-2:], dt)
            if n:
                buf[..., :n, :, :] = kv_sel[i]
            kv_pad.append(buf)
        row_bt = np.full((npg,), P, np.int32)
        row_bt[:len(row_pages)] = row_pages
        self.cache = self._prefill_jit[key](
            self.cache, jnp.asarray(ids), kv_pad,
            jnp.asarray(slot, jnp.int32), jnp.asarray(row_bt),
            jnp.asarray(int(counter), jnp.int32))

    def _export_session(self, s: _Slot) -> dict:
        """The handoff descriptor for one just-prefilled request: prompt
        + first token + sampler state + every page of computed prompt
        K/V (shared prefix pages included — the export is a read), each
        page content-hashed so the adopting side can verify the transfer
        byte-for-byte."""
        pt = self.cfg.kv_page_tokens
        prompt = self._prompts[s.request_id]
        n_pp = -(-prompt.size // pt)
        kv = self._gather_pages(s.lease.page_ids[:n_pp])
        return {"v": 1, "prompt": np.asarray(prompt, np.int32),
                "tokens": [int(t) for t in s.tokens],
                "remaining": int(s.remaining),
                "temperature": float(s.temperature),
                "top_p": float(s.top_p), "seed": int(s.seed),
                "page_tokens": int(pt), "pages": int(n_pp),
                "kv": kv, "page_hashes": hash_page_data(kv, n_pp),
                "struct": self._kv_struct()}

    def take_sessions(self) -> list[tuple[int, dict]]:
        """Drain the exported sessions (prefill-only mode): ``(request_id,
        session)`` pairs since the last call.  The serving loop ships
        each as a ``handoff`` message; a taken request's stored result is
        dropped here (its completion belongs to the adopting pool)."""
        out, self._sessions = self._sessions, []
        for rid, _ in out:
            self._results.pop(rid, None)
        return out

    def adopt_session(self, session: dict, on_token=None) -> int:
        """Queue a handed-off session for adoption: verified here —
        layout signature AND per-page content hashes, so a corrupt or
        raced transfer raises ``ValueError`` loudly without touching the
        device or poisoning the batcher — then seated into a slot on the
        next ``step()`` with a free slot and pages (strict-FIFO page
        backpressure, like ``submit``).  The seated request decodes from
        its first token on without re-prefilling; its stream stays the
        pure function of (params, prompt, budget, temperature, top_p,
        seed) the oracle locks.  Returns the local request id."""
        self._check_usable()
        if self.cfg.has_state:
            raise ValueError(
                "adopt_session seats a session from its K/V pages alone; "
                f"this configuration keeps {self.cfg.cache_kinds}")
        if self.prefill_only:
            raise ValueError("a prefill-only batcher cannot adopt "
                             "sessions (it never decode-steps)")
        if not isinstance(session, dict) or session.get("v") != 1:
            raise ValueError("malformed session descriptor")
        missing = [k for k in ("prompt", "tokens", "remaining",
                               "page_tokens", "pages", "kv",
                               "page_hashes", "struct")
                   if k not in session]
        if missing:
            # every rejection here must be the documented ValueError —
            # a KeyError would escape the serve loop's typed-error
            # bounce and crash the decode worker over one bad message
            raise ValueError(f"malformed session descriptor: missing "
                             f"key(s) {missing}")
        pt = self._pages.page_tokens
        if int(session["page_tokens"]) != pt:
            raise ValueError(
                f"session page_tokens {session['page_tokens']} != this "
                f"pool's {pt} — prefill and decode pools must agree")
        prompt = np.asarray(session["prompt"], np.int32).reshape(-1)
        tokens = [int(t) for t in session["tokens"]]
        remaining = int(session["remaining"])
        if prompt.size == 0 or len(tokens) != 1 or remaining < 1:
            raise ValueError("a handoff session carries exactly the "
                             "first token and a positive remaining "
                             f"budget (got {len(tokens)} token(s), "
                             f"remaining {remaining})")
        n_pp = -(-prompt.size // pt)
        kv = session["kv"]
        struct = self._kv_struct()
        ok_shape = int(session.get("pages", -1)) == n_pp \
            and len(kv) == len(struct)
        if ok_shape:
            for a, (shape, dt) in zip(kv, struct):
                a = np.asarray(a)
                if a.ndim < 3 or a.shape[_PAGE_AXIS] != n_pp \
                        or a.shape[:-3] + a.shape[-2:] != shape \
                        or str(a.dtype) != dt:
                    ok_shape = False
                    break
        if not ok_shape:
            raise ValueError(
                "session KV layout mismatch — the transfer raced a "
                "replica with a different model/cache geometry; "
                "rejecting the session")
        got = hash_page_data(kv, n_pp)
        want = list(session["page_hashes"])
        if got != want:
            bad = [j for j, (g, w) in enumerate(zip(got, want)) if g != w]
            raise ValueError(
                f"corrupt KV-page transfer: content hash mismatch on "
                f"page(s) {bad} of {n_pp} — rejecting the session")
        total = prompt.size + len(tokens) + remaining
        if total > self.cfg.max_position_embeddings:
            raise ValueError(
                f"session needs {total} positions, exceeding "
                f"max_position_embeddings "
                f"({self.cfg.max_position_embeddings})")
        if self._pages.pages_needed(total) > self._pages.total_pages:
            raise ValueError(
                f"session needs {self._pages.pages_needed(total)} KV "
                f"pages but the pool holds {self._pages.total_pages}")
        rid = next(self._ids)
        self._pending_adopt.append(
            (rid, {**session, "prompt": prompt, "tokens": tokens,
                   "remaining": remaining}))
        if on_token is not None:
            self._on_token[rid] = on_token
        if self.spec_k is not None:
            self._prompts[rid] = prompt[-self.spec_window:]
        return rid

    def _admit_adopts(self) -> None:
        """Seat queued session adoptions: lease pages (prefix-index
        matches need no data import — handoff composes with cross-
        request reuse), import the unmatched prompt pages' K/V, seat the
        block-table row and counters, and activate the slot mid-stream
        (first token already emitted by the prefill side, so no token is
        re-surfaced here).  Strict FIFO on page backpressure."""
        while self._pending_adopt:
            free = [i for i, s in enumerate(self.slots)
                    if s is None and i not in self._reserved]
            if not free:
                return
            rid, sess = self._pending_adopt[0]
            prompt = sess["prompt"]
            total = prompt.size + len(sess["tokens"]) + sess["remaining"]
            lease = self._pages.adopt(prompt, total)
            if lease is None:
                return          # pages free as running requests finish
            self._pending_adopt.pop(0)
            pt = self._pages.page_tokens
            n_pp = -(-prompt.size // pt)
            import_ids = lease.page_ids[lease.n_shared:n_pp]
            kv_sel = []
            if import_ids:
                sel = range(lease.n_shared, n_pp)
                kv_sel = [np.take(np.asarray(a), sel, axis=_PAGE_AXIS)
                          for a in sess["kv"]]
            # counters seat at prompt.size: the next decode step feeds
            # the session's first token and writes its K/V there, exactly
            # where a locally-prefilled slot would
            self._seat_pages_device(free[0], lease.page_ids, import_ids,
                                    kv_sel, prompt.size)
            # commit AFTER the import dispatch: only written pages are
            # ever matchable (the _prefill contract)
            self._pages.commit(lease)
            self.sessions_adopted += 1
            s = _Slot(request_id=rid, remaining=int(sess["remaining"]),
                      tokens=list(sess["tokens"]),
                      temperature=float(sess.get("temperature", 0.0)),
                      top_p=float(sess.get("top_p", 1.0)),
                      seed=int(sess.get("seed", 0)), lease=lease,
                      prompt_len=prompt.size)
            self.slots[free[0]] = s

    # -- prefix-cache cloning (warm-standby promotion; docs/robustness.md)
    def export_prefix_cache(self, max_pages: int | None = None) \
            -> dict | None:
        """Snapshot this batcher's SHARED prefix-cache pages (every
        indexed page, donor insertion order, content-hashed) for a peer
        to import — the page-transfer plane's bulk edition, ridden by
        the standby promotion clone so a healed replica keeps its
        peer's prefix hits.  None when empty.  Must run on the
        batcher's driving thread (the gather reads the live cache)."""
        entries = self._pages.export_index()[:max_pages]
        if not entries:
            return None
        pids = [pid for _, pid in entries]
        kv = self._gather_pages(pids)
        return {"v": 1, "keys": [k for k, _ in entries],
                "pages": len(pids), "kv": kv,
                "page_hashes": hash_page_data(kv, len(pids)),
                "page_tokens": int(self._pages.page_tokens),
                "struct": self._kv_struct()}

    def import_prefix_cache(self, export: dict | None) -> int:
        """Adopt a peer's cloned prefix-cache pages into this (fresh)
        pool as refcount-0 cached pages — matchable by the very next
        admission, evictable under pressure.  Layout + per-page hashes
        verified first (corrupt transfers raise, they never reach the
        device); capacity truncation keeps chains reachable (donor
        order).  Returns the number of pages imported."""
        if not export:
            return 0
        if int(export.get("page_tokens", -1)) != self._pages.page_tokens \
                or export.get("struct") != self._kv_struct():
            raise ValueError("prefix-cache transfer layout mismatch — "
                             "donor and importer cache geometries differ")
        n = int(export["pages"])
        kv = export["kv"]
        if hash_page_data(kv, n) != list(export["page_hashes"]):
            raise ValueError("corrupt prefix-cache transfer: content "
                             "hash mismatch — rejecting the import")
        mapping = self._pages.adopt_cached(export["keys"])
        if not mapping:
            return 0
        pos_of = {k: i for i, k in enumerate(export["keys"])}
        keys = list(mapping)
        sel = [pos_of[k] for k in keys]
        kv_sel = [np.take(np.asarray(a), sel, axis=_PAGE_AXIS)
                  for a in kv]
        # slot = max_batch: the seat drops — this dispatch only writes
        # the imported pages into the pools
        self._seat_pages_device(self.max_batch, [],
                                [mapping[k] for k in keys], kv_sel, 0)
        return len(mapping)

    # -- admission ---------------------------------------------------------
    def has_free_slot(self) -> bool:
        """True while another ``submit`` would find a slot: queued-but-
        unadmitted requests (and the slot reserved by an in-flight
        chunked admission) count against the free slots, so a driver
        looping ``while b.has_free_slot(): b.submit(...)`` terminates."""
        free = sum(s is None and i not in self._reserved
                   for i, s in enumerate(self.slots))
        return len(self._pending) + len(self._pending_adopt) < free

    def submit(self, prompt_ids, max_new_tokens: int, *,
               temperature: float = 0.0, top_p: float = 1.0,
               seed: int = 0, on_token=None) -> int:
        """Queue a request; it is admitted into a slot on the next
        ``step()`` with a free slot.  Returns the request id.

        ``temperature=0`` (default) decodes greedily — token-identical to
        a solo ``greedy_generate`` run.  ``temperature>0`` samples from
        the nucleus ``top_p`` at that temperature, keyed by ``seed``:
        the output is a pure function of (params, prompt, budget,
        temperature, top_p, seed) — batch company never changes it.

        ``on_token(request_id, token)`` streams every COMMITTED token in
        emission order, from inside the ``step()`` that commits it — the
        hook a serving loop uses to forward deltas before the request
        finishes.  Tokens a block/speculative dispatch computes but
        discards (past eos or budget) are never surfaced.  The callback
        runs on the driving thread and must be cheap and must not raise:
        an exception propagates out of ``step()`` and poisons the batcher
        exactly like a device failure (the dispatch that produced the
        token already consumed the donated cache)."""
        self._check_usable()
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} "
                "(the greedy-exact contract has no 0-token decode)")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if not 0 < top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if not -2**31 <= seed < 2**31:
            raise ValueError(f"seed must fit int32, got {seed}")
        total = prompt.size + max_new_tokens
        if total > self.cfg.max_position_embeddings:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) = {total} exceeds "
                f"max_position_embeddings "
                f"({self.cfg.max_position_embeddings})")
        if self._pages.pages_needed(total) > self._pages.total_pages:
            # liveness guard: a request the WHOLE pool cannot hold would
            # wait at the head of the queue forever (prefix sharing
            # could shrink its need, but cached pages are evictable and
            # cannot be promised at submit time)
            raise ValueError(
                f"request needs {self._pages.pages_needed(total)} KV "
                f"pages ({total} tokens at {self._pages.page_tokens}/"
                f"page) but the pool holds {self._pages.total_pages}")
        rid = next(self._ids)
        self._pending.append((rid, prompt, int(max_new_tokens),
                              float(temperature), float(top_p), int(seed)))
        if on_token is not None:
            self._on_token[rid] = on_token
        if self.spec_k is not None:   # only drafting reads the history,
            # and only its trailing window of it
            self._prompts[rid] = prompt[-self.spec_window:]
        elif self.prefill_only:       # session export needs the FULL
            # prompt (page chain keys + the decode pool's replay input)
            self._prompts[rid] = prompt
        return rid

    def _last_logits(self, params, cache, tokens, true_len):
        """A padded prefill's forward: ``(logits at each row's last true
        position [rows, V], cache, expert stats)``.  With recurrent state
        the model is told the true lengths (the state is taken there) and
        computes the head at that position only."""
        if self.cfg.has_state:
            logits, cache, stats = _apply(self.model, params, cache,
                                          tokens, lengths=true_len)
            return logits[:, 0], cache, stats
        logits, cache, stats = _apply(self.model, params, cache, tokens)
        return jnp.take_along_axis(
            logits, (true_len - 1)[:, None, None], axis=1)[:, 0], cache, \
            stats

    def _prefill_groups(self, buckets: dict) -> list[list]:
        """The admission groups of one round: same-bucket requests
        together, cut to at most ``prefill_rows_max`` rows a dispatch."""
        cap = self.prefill_rows_max
        return [reqs[i:i + (cap or len(reqs))] for reqs in buckets.values()
                for i in range(0, len(reqs), cap or len(reqs))]

    def _admit(self) -> list[int]:
        """Fill free slots from the pending queue; returns the ids of
        requests that finished AT admission (1-token budget or immediate
        eos) so ``step()`` can report them.

        Each taken request first LEASES pages — a prefix-index match
        plus freshly allocated tail pages — and a request the pool
        cannot serve right now blocks the queue (strict-FIFO page
        backpressure: pages free as running requests finish, so the
        head admits eventually; ``submit`` already rejected requests
        larger than the whole pool, so this cannot deadlock).

        Burst admission: requests taken this round are grouped by
        power-of-two bucket of their TAIL length — after its prefix
        match a 10k-token prompt with a cached system prompt shares the
        short-tail executable, which is the TTFT win — and each group
        shares ONE batched prefill dispatch: O(distinct buckets) device
        dispatches for the round, not O(requests).  Tails beyond
        ``prefill_chunk`` stream through the at-most-one in-flight
        chunked admission, one chunk per step (``_advance_inflight``),
        with their slot reserved until the final chunk lands.  The loop
        repeats while finished-at-admission requests keep freeing
        slots."""
        done: list[int] = []
        self._admit_adopts()   # handed-off sessions seat before new
        # prompts: their prefill compute is already spent elsewhere
        if self._inflight is not None:
            done.extend(self._advance_inflight())
        C = self.prefill_chunk
        while self._pending:
            free = [i for i, s in enumerate(self.slots)
                    if s is None and i not in self._reserved]
            if not free:
                break
            taken_idx: list[int] = []
            whole = []                           # (req, lease)
            blocked = False
            for j, req in enumerate(self._pending):
                if len(free) - len(whole) == 0:  # every free slot claimed
                    break
                prompt, budget = req[1], req[2]
                # peek order matters: `prompt.size > C` first, so the
                # hash-chain peek only runs for prompts that could even
                # need chunking — not for every cache-hot short prompt
                # on every step while an admission streams
                if C is not None and self._inflight is not None \
                        and prompt.size > C \
                        and prompt.size - self._pages.match_tokens(prompt) \
                        > C:
                    # one chunked admission at a time; SKIP before
                    # leasing (a trial lease's allocation could evict
                    # cached prefix pages an immediate release cannot
                    # restore) — shorts behind it still admit while the
                    # first long prompt streams
                    continue
                lease = self._pages.admit(prompt, prompt.size + budget)
                if lease is None:
                    blocked = True
                    break
                if C is not None and prompt.size - lease.tail_start > C:
                    if self._inflight is not None:
                        # the peek said whole-prompt but the index moved
                        # (shouldn't happen within one round); stay safe
                        self._pages.release(lease)
                        continue
                    slot = free.pop()            # reserve from the tail
                    self._reserved.add(slot)
                    self._inflight = {"req": req, "slot": slot,
                                      "lease": lease, "done_chunks": 0}
                    taken_idx.append(j)
                    # first slice; >= 1 full chunk precedes the final
                    # call, so this cannot finish or emit a token
                    self._advance_inflight()
                else:
                    taken_idx.append(j)
                    whole.append((req, lease))
            if not taken_idx:
                break
            for j in reversed(taken_idx):
                del self._pending[j]
            groups: dict[int, list] = {}
            for req, lease in whole:
                Tp = min(_next_pow2(req[1].size - lease.tail_start),
                         self.cfg.max_position_embeddings)
                groups.setdefault(Tp, []).append((req, lease))
            free_iter = iter(free)
            # (slot, req-fields, first_token, lease, prompt length)
            admitted = []
            for reqs in self._prefill_groups(groups):
                slots = [next(free_iter) for _ in reqs]
                firsts = self._prefill(
                    [(req, lease, lease.tail_start)
                     for req, lease in reqs], slots)
                for j, (req, lease) in enumerate(reqs):
                    rid, prompt, budget, temp, top_p, seed = req
                    admitted.append((slots[j], (rid, budget, temp, top_p,
                                                seed), int(firsts[j]),
                                     lease, prompt.size))
            for slot, (rid, budget, temp, top_p, seed), tok, lease, n \
                    in admitted:
                self._emit_token(rid, tok)
                s = _Slot(request_id=rid, remaining=budget - 1,
                          tokens=[tok], temperature=temp, top_p=top_p,
                          seed=seed, lease=lease, prompt_len=n)
                if s.remaining <= 0 or tok == self.eos_id:
                    self._finish(slot, s)   # slot stays free; loop refills
                    done.append(rid)
                else:
                    self.slots[slot] = s
            if blocked:
                break
        return done

    def _state_rows(self, rows: int) -> list:
        """Zeroed recurrent state for ``rows`` fresh rows: one ``[rows,
        ...]`` array per state leaf of the cache, in its traversal order
        (``[]`` for a model without such layers)."""
        return [jnp.zeros((rows,) + leaf.shape[1:], leaf.dtype)
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    self.cache)[0] if is_state_leaf(path)]

    def _prefill(self, entries, slots: list[int],
                 state_rows: list | None = None) -> np.ndarray:
        """THE prefill: one fused dispatch per admission group that
        (1) prefills every row's TAIL tokens (positions after its
        prefix-cache match) straight into the slot's leased pages via a
        per-row block-table view over the shared pool — shared prefix
        pages are only READ, the read-only/copy-on-write contract —
        (2) selects each row's first token at its true last prompt
        position, and (3) scatters the rows' block tables and rewound-
        to-true-total counters into the batch cache: admission lands in
        ONE executable per (pow2 tail bucket, pow2 group size), no side
        cache, no separate scatter dispatch.  One executable serves
        greedy and sampled requests (``_select_tokens`` reduces to argmax
        at temperature 0).

        Why padding is exact: prefill attention is causal, so pad tokens
        never influence a true last position's logits (selected per row
        at ``true_len - 1``), and a conv or retention layer takes its
        state at the row's true length (the model's ``lengths``), where no
        pad token has entered it; each row's cache counters are then REWOUND
        to its true total, after which the positional visibility mask
        hides every pad position (``k_pos > q_pos``) until the decode
        loop overwrites it with a real token's K/V in the same forward
        that first makes it visible.

        ``entries`` = ``[(req_tuple, lease, start)]`` where ``start`` is
        the first prompt position fed here (the lease's tail start, or
        past the already-streamed chunks for a chunked admission's
        final call).  ``state_rows`` is the recurrent state that chunked
        admission carried to here (``_state_rows``' layout); None = the
        rows start from zero state.  The rows' state after their true
        last token is scattered into the batch's state rows with the
        tables.  Pad rows carry all-sentinel block tables (their
        writes drop) and slot ``max_batch`` (their scatter drops).
        Commits every lease — prefix-index insertion — after the
        dispatch, so only ALREADY-COMPUTED pages are ever matchable.
        Where a decode step is queued ahead, the dispatch goes behind it
        and that step is consumed before this one's tokens are fetched
        (``step()`` then fetches no other decode step: the rows seated
        here join the next dispatch, made before it returns)."""
        cfgC = self.cfg.max_position_embeddings
        P = self._pages.total_pages
        npg = self._table_pages
        Tp = min(_next_pow2(max(req[1].size - start
                                for req, _, start in entries)), cfgC)
        rp = _next_pow2(len(entries))
        carried = state_rows is not None
        key = ("final", Tp, rp, carried) if carried else ("final", Tp, rp)
        if key not in self._prefill_jit:
            def final_fn(params, cache, tokens, row_bt, row_start,
                         true_len, true_tot, slot_ids, seeds, temps,
                         top_ps, state_rows):
                row_cache = _row_view(cache, row_bt, row_start,
                                      state_rows if carried else None)
                last, new_cache, stats = self._last_logits(
                    params, row_cache, tokens, true_len)
                first = _select_tokens(
                    last, seeds, jnp.zeros_like(true_len), temps, top_ps)

                def back(path, b_leaf, r_leaf):
                    k = getattr(path[-1], "key", None)
                    if is_state_leaf(path):
                        return b_leaf.at[slot_ids].set(r_leaf, mode="drop")
                    if k == "block_table":
                        m = jnp.moveaxis(b_leaf, -2, 0)
                        v = jnp.broadcast_to(
                            row_bt.reshape((row_bt.shape[0],)
                                           + (1,) * (m.ndim - 2)
                                           + (row_bt.shape[-1],)),
                            row_bt.shape[:1] + m.shape[1:])
                        return jnp.moveaxis(
                            m.at[slot_ids].set(v, mode="drop"), 0, -2)
                    if k in ("index", "pos"):
                        m = jnp.moveaxis(b_leaf, -1, 0)
                        v = jnp.broadcast_to(
                            true_tot.reshape(true_tot.shape
                                             + (1,) * (m.ndim - 1)),
                            true_tot.shape + m.shape[1:]).astype(m.dtype)
                        return jnp.moveaxis(
                            m.at[slot_ids].set(v, mode="drop"), 0, -1)
                    return r_leaf   # pool leaves: take the prefill writes

                return _pack(first, stats), \
                    jax.tree_util.tree_map_with_path(back, cache, new_cache)

            self._prefill_jit[key] = self._jit(key, final_fn,
                                               donate_argnums=(1,))
        self.prefill_dispatches += 1
        self.carried_prefills += carried
        self.grouped_matmul_calls += self._step_grouped_matmul_calls
        if self.cfg.has_state:
            self.state_rows_seated += len(entries)
        with self._spans(_obs.BATCHER_PREFILL_DISPATCH):
            row_bt = np.full((rp, npg), P, np.int32)
            row_start = np.zeros((rp,), np.int32)
            tokens = np.zeros((rp, Tp), np.int32)
            true_len = np.ones((rp,), np.int32)
            true_tot = np.ones((rp,), np.int32)
            slot_a = np.full((rp,), self.max_batch, np.int32)
            seed_a = np.zeros((rp,), np.int32)
            temp_a = np.zeros((rp,), np.float32)
            top_a = np.ones((rp,), np.float32)
            for j, (req, lease, start) in enumerate(entries):
                _, prompt, _, temp, top_p, seed = req
                tail = prompt[start:]
                row_bt[j, :len(lease.page_ids)] = lease.page_ids
                row_start[j] = start
                tokens[j, :tail.size] = tail
                true_len[j] = tail.size
                true_tot[j] = prompt.size
                slot_a[j] = slots[j]
                seed_a[j] = seed
                temp_a[j] = temp
                top_a[j] = top_p
            firsts, self.cache = self._prefill_jit[key](
                self.params, self.cache, tokens, row_bt,
                jnp.asarray(row_start), jnp.asarray(true_len),
                jnp.asarray(true_tot), jnp.asarray(slot_a),
                jnp.asarray(seed_a), jnp.asarray(temp_a),
                jnp.asarray(top_a), state_rows if carried else [])
        for _, lease, _ in entries:
            self._pages.commit(lease)
        if self._ahead is not None:
            # this prefill lies BEHIND a queued step on the device (it
            # writes the group's leased pages and free slots' rows, where
            # that step writes nothing).  Results are fetched in device
            # order: the step is consumed, and its tokens emitted, before
            # the prefill is waited for
            self._settled = self._plain_step(stand_down="admission")
        with self._spans(_obs.BATCHER_PREFILL_FETCH):
            return self._fetch(firsts, prefill=True)

    def _chunk_jit(self):
        """One fixed-chunk prefill executable: streams a chunk of
        the in-flight admission's tail into its leased pages (batch
        block tables/counters untouched — the slot only goes live at
        the final :meth:`_prefill` call).  The admission's recurrent
        state goes in and comes out beside the cache (``_state_rows``'
        layout): the reserved slot's own state row is no place for it,
        because the decode steps in between run every row."""
        C = self.prefill_chunk
        key = ("chunk", C)
        if key not in self._prefill_jit:
            def chunk_fn(params, cache, tokens_row, row_bt, start,
                         state_rows):
                row_cache = _row_view(cache, row_bt, start, state_rows)
                new_cache = _apply(self.model, params, row_cache,
                                   tokens_row)[1]
                state_out = []

                def back(p, b, r):
                    k = getattr(p[-1], "key", None)
                    if is_state_leaf(p):
                        state_out.append(r)
                        return b
                    return b if k in ("index", "pos", "block_table") else r

                return jax.tree_util.tree_map_with_path(
                    back, cache, new_cache), state_out

            self._prefill_jit[key] = self._jit(key, chunk_fn,
                                               donate_argnums=(1,))
        return self._prefill_jit[key]

    def _advance_inflight(self) -> list[int]:
        """Advance the in-flight chunked admission by ONE chunk (the
        time slice) streamed straight into the slot's leased pages, or
        finish it with the bucketed :meth:`_prefill` call on the
        remainder.  Long-context admission therefore costs one extra
        dispatch per decode step instead of stalling every running slot
        for the whole chunk loop — O(chunk x max_len) transient
        attention memory per slice."""
        inf = self._inflight
        C = self.prefill_chunk
        req = inf["req"]
        rid, prompt, budget, temp, top_p, seed = req
        lease = inf["lease"]
        n_full = (prompt.size - lease.tail_start - 1) // C
        i = inf["done_chunks"]
        if i < n_full:
            start = lease.tail_start + i * C
            row_bt = np.full((1, self._table_pages),
                             self._pages.total_pages, np.int32)
            row_bt[0, :len(lease.page_ids)] = lease.page_ids
            if "state" not in inf:
                inf["state"] = self._state_rows(1)
            with self._spans(_obs.BATCHER_PREFILL_DISPATCH):
                self.cache, inf["state"] = self._chunk_jit()(
                    self.params, self.cache, prompt[None, start:start + C],
                    row_bt, np.asarray([start], np.int32), inf["state"])
            inf["done_chunks"] += 1
            return []
        slot = inf["slot"]
        self._reserved.discard(slot)
        firsts = self._prefill(
            [(req, lease, lease.tail_start + n_full * C)], [slot],
            state_rows=inf["state"] if self.cfg.has_state else None)
        self._inflight = None
        tok = int(firsts[0])
        self._emit_token(rid, tok)
        s = _Slot(request_id=rid, remaining=budget - 1, tokens=[tok],
                  temperature=temp, top_p=top_p, seed=seed, lease=lease,
                  prompt_len=prompt.size)
        if s.remaining <= 0 or tok == self.eos_id:
            self._finish(slot, s)
            return [rid]
        self.slots[slot] = s
        return []

    def _park_slot(self, i: int) -> None:
        """A finished slot's pages return to the pool, but
        the batch executables keep stepping every row — park the row by
        setting its cache counters to max_len so its garbage writes hit
        the position guard and DROP instead of landing in pages now
        owned by someone else (the block-table row itself is replaced
        wholesale at the slot's next admission).  The row's recurrent
        state is cleared with it.  Dispatched by ``_finish``, or ahead of
        it by ``_plain_step`` for a row that ends by budget."""
        key = ("park",)
        if key not in self._prefill_jit:
            Cmax = self.cfg.max_position_embeddings

            def park_fn(cache, slot):
                def f(path, leaf):
                    k = getattr(path[-1], "key", None)
                    if k in ("index", "pos"):
                        m = jnp.moveaxis(leaf, -1, 0)
                        return jnp.moveaxis(m.at[slot].set(Cmax), 0, -1)
                    if is_state_leaf(path):
                        return leaf.at[slot].set(0)
                    return leaf
                return jax.tree_util.tree_map_with_path(f, cache)

            self._prefill_jit[key] = self._jit(key, park_fn,
                                               donate_argnums=(0,))
        self.cache = self._prefill_jit[key](self.cache,
                                            jnp.asarray(i, jnp.int32))

    def _finish(self, i: int, s: _Slot) -> None:
        self._results[s.request_id] = np.asarray(s.tokens, np.int32)
        self._prompts.pop(s.request_id, None)
        self._on_token.pop(s.request_id, None)
        self.slots[i] = None
        if s.lease is not None:
            self._pages.release(s.lease)
            s.lease = None
        if not s.parked:    # else parked behind its last step, ahead
            self._park_slot(i)

    # -- decode ------------------------------------------------------------
    def step(self) -> list[int]:
        """Admit pending requests into free slots, run ONE decode step for
        every active slot, and return every request id that finished —
        whether during decode or already at admission.

        If a device dispatch raises (OOM, preemption, a lost device),
        the batcher is marked unusable — the failing executable had
        already donated the cache buffer, so the instance cannot be
        resumed — and every later call raises ``RuntimeError`` naming
        the original failure."""
        return self._guarded(self._step_inner)

    def _guarded(self, dispatching, **kwargs) -> list[int]:
        """Run ``dispatching`` (a method that dispatches device work),
        marking the batcher unusable if it raises."""
        self._check_usable()
        try:
            return dispatching(**kwargs)
        except Exception as e:
            self._poisoned = f"{type(e).__name__}: {e}"
            raise

    def _history(self, s: "_Slot", prompt: np.ndarray,
                 W: int) -> np.ndarray:
        """Trailing ``W`` tokens of one slot's (prompt + generated)
        history, host-side int32.  Slices BEFORE concatenating: the
        window bound must hold for the copies too, or a 100k-token
        context still pays O(history)/step."""
        tail = np.asarray(s.tokens[-W:], np.int32)
        need = W - tail.size
        if need <= 0:
            return tail
        return np.concatenate([prompt[-need:].astype(np.int32), tail])

    def _draft(self, s: "_Slot", prompt: np.ndarray) -> np.ndarray:
        """Prompt-lookup draft for one slot: continuation of the most
        recent occurrence of the request's final ``spec_ngram`` tokens in
        its own (prompt + generated) history; empty when no match.  Host-
        side numpy — drafting is control flow, not device work."""
        g, k = self.spec_ngram, self.spec_k
        h = self._history(s, prompt, self.spec_window)
        if h.size <= g:
            return h[:0]
        pat = h[-g:]
        win = np.lib.stride_tricks.sliding_window_view(h, g)[:-1]
        hits = np.flatnonzero((win == pat).all(axis=1))
        if hits.size == 0:
            return h[:0]
        start = int(hits[-1]) + g
        cont = h[start:start + k]
        if 0 < cont.size < k:       # repeat the tail past known history
            cont = np.concatenate(
                [cont, np.full(k - cont.size, cont[-1], h.dtype)])
        return cont.astype(np.int32)

    def _verify_jit(self):
        """ONE fused verify executable for the lifetime: ``k+1``
        positions per row at per-row cache offsets.  Per-row acceptance
        ``a_i`` = leading drafted tokens equal to the model's own argmax
        (restricted to that row's valid draft length ``d_i``); the
        boundary logits then yield the bonus token through the same
        greedy/nucleus selector as the plain step.  Cache counters come
        back adjusted to each row's committed position — stale K/V past
        it stays masked by positional visibility until overwritten (the
        ``rewind_cache`` contract, per-row)."""
        if "verify" in self._prefill_jit:
            return self._prefill_jit["verify"]
        K = self.spec_k

        def verify_fn(params, cache, toks, d, seeds, steps0, temps,
                      top_ps):
            logits, new_cache, stats = _apply(self.model, params, cache,
                                              toks)      # [B, K+1, V]
            greedy = jnp.argmax(logits, axis=-1)
            ok = (toks[:, 1:] == greedy[:, :-1]) \
                & (jnp.arange(K)[None, :] < d[:, None])
            a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1),
                        axis=1)                          # [B] accepted
            bound = jnp.take_along_axis(
                logits, a[:, None, None], axis=1)[:, 0]  # [B, V]
            bonus = _select_tokens(bound, seeds, steps0 + a, temps,
                                   top_ps)
            # counters advanced K+1 in apply; commit = pre + a + 1
            cache = jax.tree_util.tree_map_with_path(
                lambda p, leaf: leaf + (a - K)
                if getattr(p[-1], "key", None) in ("index", "pos")
                else leaf, new_cache)
            return a, _pack(bonus, stats), cache

        self._prefill_jit["verify"] = self._jit("verify", verify_fn,
                                                donate_argnums=(1,))
        return self._prefill_jit["verify"]

    def _count_step_traffic(self, steps: int = 1, tokens_per_row: int = 1,
                            rows: list | None = None):
        """Account one decode dispatch of ``steps`` steps of
        ``tokens_per_row`` tokens in ``kv_pages_read`` /
        ``kv_pages_viewed``, ``state_bytes_moved`` and
        ``grouped_matmul_calls``: host arithmetic over the lengths of the
        dispatch's ``rows`` (None = the seated slots), no device work."""
        self.state_bytes_moved += steps * self._state_step_bytes
        self.grouped_matmul_calls += steps * self._step_grouped_matmul_calls
        if not self._table_pages:
            return
        pt, C = self.cfg.kv_page_tokens, self.cfg.max_position_embeddings
        lens = [s.prompt_len + len(s.tokens) + tokens_per_row - 1
                for s in (self.slots if rows is None else rows)
                if s is not None]
        self.kv_pages_read += sum(-(-min(n + k, C) // pt)
                                  for n in lens for k in range(steps))
        if tokens_per_row == 1 and self._attends_in_place:
            self.kv_pages_viewed += steps * len(lens) * (C // pt)

    def _spec_step(self) -> list[int]:
        """One speculative decode step for every active slot: propose
        (draft model when armed, else host-side prompt lookup), then one
        fused verify dispatch commits each row's agreeing prefix plus
        the bonus token."""
        K = self.spec_k
        B = self.max_batch
        dm = self._draft_model
        with self._spans(_obs.BATCHER_DECODE_DISPATCH):
            toks = np.zeros((B, K + 1), np.int32)
            d = np.zeros((B,), np.int32)
            elig: list[int] = []
            if dm is not None:
                buf = np.zeros((B, dm.window + K), np.int32)
                lens = np.ones((B,), np.int32)
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                toks[i, :] = s.tokens[-1]
                if s.temperature <= 0 and s.remaining > 1:
                    # sampled rows keep the draft-0 fallback: their token
                    # still comes from the verify dispatch's boundary logits
                    if dm is not None:
                        h = self._history(s, self._prompts[s.request_id],
                                          dm.window)
                        buf[i, :h.size] = h
                        lens[i] = h.size
                        elig.append(i)
                        continue
                    dr = self._draft(s, self._prompts[s.request_id])
                    di = min(dr.size, s.remaining - 1)
                    if di > 0:
                        toks[i, 1:1 + dr.size] = dr
                        d[i] = di
            if dm is not None and elig:
                # ONE scanned draft dispatch proposes K tokens for every
                # eligible row; ineligible rows ride along masked (d=0)
                props = dm.propose(buf, lens, K)
                self.draft_dispatches += 1
                for i in elig:
                    s = self.slots[i]
                    toks[i, 1:1 + K] = props[i]
                    d[i] = min(K, s.remaining - 1)
        if not d.any():
            # nothing drafted anywhere (all-sampled traffic, novel text,
            # or every slot at its last token): fall through to the plain
            # step — the (K+1)-position verify would pay ~(K+1)x compute
            # to commit exactly one token per slot
            return self._plain_step()
        self.decode_dispatches += 1
        self.decode_steps += 1
        self.decode_ahead_standdowns["alternative"] += 1
        with self._spans(_obs.BATCHER_DECODE_DISPATCH):
            self._count_step_traffic(tokens_per_row=K + 1)
            a, bonus, self.cache = self._verify_jit()(
                self.params, self.cache, jnp.asarray(toks), jnp.asarray(d),
                jnp.asarray([s.seed if s else 0 for s in self.slots],
                            jnp.int32),
                jnp.asarray([len(s.tokens) if s else 0 for s in self.slots],
                            jnp.int32),
                jnp.asarray([s.temperature if s else 0.0
                             for s in self.slots], jnp.float32),
                jnp.asarray([s.top_p if s else 1.0 for s in self.slots],
                            jnp.float32))
        with self._spans(_obs.BATCHER_DECODE_FETCH):
            a, bonus = np.asarray(a), self._fetch(bonus)
        done = []
        with self._spans(_obs.BATCHER_EMIT):
            self.spec_proposed += int(d.sum())
            self.spec_accepted += int(a.sum())
            for i in np.flatnonzero(d):
                self._accept_lens.append(int(a[i]))
            if len(self._accept_lens) > 65536:   # unmetered batcher: bound
                del self._accept_lens[:-4096]
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                new = list(toks[i, 1:1 + a[i]]) + [int(bonus[i])]
                for tok in new:
                    s.tokens.append(int(tok))
                    self._emit_token(s.request_id, int(tok))
                    s.remaining -= 1
                    if s.remaining <= 0 or tok == self.eos_id:
                        done.append(s.request_id)
                        self._finish(i, s)
                        break
        return done

    def _step_inner(self) -> list[int]:
        # the prefill spans inside suspend this one (observability.span)
        with self._spans(_obs.BATCHER_ADMIT):
            done = self._admit()
        if self.prefill_only:
            # prefill-pool posture: a seated request's prompt KV is
            # computed — export the session for handoff instead of ever
            # decode-stepping it.  The release inside _finish keeps the
            # pool's prefix index warm (full prompt pages park in the
            # LRU, matchable by the next same-system-prompt admission).
            with self._spans(_obs.BATCHER_EMIT):
                for i, s in enumerate(self.slots):
                    if s is None or i in self._reserved:
                        continue
                    self._sessions.append((s.request_id,
                                           self._export_session(s)))
                    self.sessions_exported += 1
                    self._finish(i, s)
            return done
        settled, self._settled = self._settled, None
        if settled is not None:
            # an admission consumed the queued step (``_prefill``): that
            # was this call's decode step
            done = settled + done
        elif not any(self.slots):
            return done
        elif self.spec_k is not None:
            return done + self._spec_step()
        else:
            K = self._block_size()
            if K > 1:
                return done + self._block_step(K)
            done += self._plain_step()
        if self._ahead is None and self._stands_down() is None:
            # the step stood down for rows seated this turn (prefilled
            # behind it, or adopted): their tokens are on the host now, so
            # the next step is dispatched before the caller's turn goes on,
            # not after it, and the device starts at once
            with self._spans(_obs.BATCHER_DECODE_DISPATCH):
                self._ahead = self._dispatch_step()
        return done

    def _block_size(self) -> int:
        """How many decode steps the next dispatch may scan: bounded by
        ``decode_block_steps``, the minimum remaining budget over active
        slots (so no slot overshoots), and rounded down to a power of two
        (compile count O(log block)).

        Admission latency rules: an in-flight chunked prefill always
        forces single steps (its time slice is one chunk per ``step()``).
        A queued-but-unadmittable request forces single steps only when
        ``eos_id`` is set — an eos can free a slot at ANY step, and a
        block would sit on that slot until its end.  Without eos, no
        slot can free before the minimum remaining budget, so scanning
        up to that bound delays the queued request by exactly zero
        steps."""
        if self.decode_block_steps is None:
            return 1
        if self._inflight is not None:
            return 1
        if self._pending and self.eos_id is not None:
            return 1
        rem = min(s.remaining for s in self.slots if s is not None)
        cand = min(self.decode_block_steps, rem)
        if cand < 2:
            return 1
        return 1 << (cand.bit_length() - 1)

    def _block_jit(self, K: int, sampled: bool):
        """The K-step scanned decode executable: the scan body is the
        plain step verbatim, so the emitted tokens are identical to K
        separate dispatches — only the host round trips differ."""
        key = ("block", K, sampled)
        if key in self._prefill_jit:
            return self._prefill_jit[key]
        model = self.model

        def block_out(seq, stats):
            """``[B, K]`` tokens; with experts, packed with the K steps'
            stats summed."""
            return _pack(seq.swapaxes(0, 1),
                         None if stats is None else jnp.sum(stats, axis=0))

        if sampled:
            def block_fn(params, cache, tokens, seeds, steps0, temps,
                         top_ps):
                def body(carry, i):
                    toks, cache = carry
                    nxt, stats, cache = _decode_one_sampled(
                        model, params, cache, toks, seeds, steps0 + i,
                        temps, top_ps)
                    return (nxt, cache), (nxt, stats)

                (_, cache), (seq, stats) = jax.lax.scan(
                    body, (tokens, cache), jnp.arange(K))
                return block_out(seq, stats), cache
        else:
            def block_fn(params, cache, tokens):
                def body(carry, _):
                    toks, cache = carry
                    nxt, stats, cache = _decode_one_greedy(
                        model, params, cache, toks)
                    return (nxt, cache), (nxt, stats)

                (_, cache), (seq, stats) = jax.lax.scan(
                    body, (tokens, cache), None, length=K)
                return block_out(seq, stats), cache

        self._prefill_jit[key] = self._jit(key, block_fn,
                                           donate_argnums=(1,))
        return self._prefill_jit[key]

    def _block_step(self, K: int) -> list[int]:
        """ONE dispatch, K committed decode steps.  A row that emits
        ``eos_id`` mid-block keeps scanning (its later tokens are
        discarded here and their K/V lies in its own pages, which return
        to the pool when it finishes) — wasted compute is bounded by K-1
        row-steps, the price of the K× dispatch amortization."""
        done: list[int] = []
        self.decode_dispatches += 1
        self.decode_steps += K
        self.decode_ahead_standdowns["alternative"] += 1
        with self._spans(_obs.BATCHER_DECODE_DISPATCH):
            self._count_step_traffic(steps=K)
            tokens = jnp.asarray([s.tokens[-1] if s else 0
                                  for s in self.slots], jnp.int32)
            if any(s is not None and s.temperature > 0 for s in self.slots):
                seq, self.cache = self._block_jit(K, True)(
                    self.params, self.cache, tokens,
                    jnp.asarray([s.seed if s else 0 for s in self.slots],
                                jnp.int32),
                    jnp.asarray([len(s.tokens) if s else 0
                                 for s in self.slots], jnp.int32),
                    jnp.asarray([s.temperature if s else 0.0
                                 for s in self.slots], jnp.float32),
                    jnp.asarray([s.top_p if s else 1.0 for s in self.slots],
                                jnp.float32))
            else:
                seq, self.cache = self._block_jit(K, False)(
                    self.params, self.cache, tokens)
        with self._spans(_obs.BATCHER_DECODE_FETCH):
            seq = self._fetch(seq, (self.max_batch, K))
        with self._spans(_obs.BATCHER_EMIT):
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                for tok in seq[i]:
                    tok = int(tok)
                    s.tokens.append(tok)
                    self._emit_token(s.request_id, tok)
                    s.remaining -= 1
                    if s.remaining <= 0 or tok == self.eos_id:
                        done.append(s.request_id)
                        self._finish(i, s)
                        break
        return done

    def _stands_down(self, rows: list | None = None) -> str | None:
        """Why the next plain decode step is not decided yet, one of
        :data:`STANDDOWNS`, or None where it is.  ``rows`` are the slots'
        rows of the step about to be fetched, which the next would be
        dispatched behind, fed its tokens as they lie on the device; None
        = no step awaits its fetch, and the next is fed the host's tokens.
        A batcher that decides each dispatch from the last one's tokens
        (``speculative_k``, ``decode_block_steps``) never knows; nor one
        whose rows an ``eos_id`` can end at any step, or with a chunked
        admission in flight (it takes its slot at a step of its own); a
        sampled row's step needs its host-side sampler state; a row seated
        since that step was dispatched has its token on the host, not in
        the step's output; and with no seated row left after it there is
        no next step.  Otherwise nothing can join before the next step,
        what leaves it leaves by budget, and a free slot changes nothing:
        a step runs every row, seated or parked."""
        if self.spec_k is not None or self.decode_block_steps is not None:
            return "alternative"
        if self.eos_id is not None:
            return "eos"
        if self._inflight is not None:
            return "chunked"
        if any(s is not None and s.temperature > 0 for s in self.slots):
            return "sampled"
        if rows is not None and any(s is not None and s is not r
                                    for s, r in zip(self.slots, rows)):
            return "admission"
        if not any(s is not None and (rows is None or s.remaining > 1)
                   for s in self.slots):
            return "idle"
        return None

    @property
    def step_queued(self) -> bool:
        """Whether a dispatched decode step awaits its fetch: the device
        has work whatever the host does next, so a serving loop need not
        wait for a request on its behalf."""
        return self._ahead is not None

    def _dispatch_step(self) -> tuple:
        """Dispatch one plain decode step of the seated rows, fed the
        host's tokens: its packed tokens, on the device, and its rows."""
        if any(s is not None and s.temperature > 0 for s in self.slots):
            tokens = jnp.asarray([s.tokens[-1] if s else 0
                                  for s in self.slots], jnp.int32)
            nxt, self.cache = self._step_sample(
                self.params, self.cache, tokens,
                jnp.asarray([s.seed if s else 0 for s in self.slots],
                            jnp.int32),
                jnp.asarray([len(s.tokens) if s else 0
                             for s in self.slots], jnp.int32),
                jnp.asarray([s.temperature if s else 0.0
                             for s in self.slots], jnp.float32),
                jnp.asarray([s.top_p if s else 1.0 for s in self.slots],
                            jnp.float32))
        else:
            # padded to the packed length for a model with experts
            # (``step_greedy``)
            tokens = np.zeros(
                self.max_batch + _moe.STATS_PER_LAYER
                * self.cfg.num_expert_layers, np.int32)
            tokens[:self.max_batch] = [s.tokens[-1] if s else 0
                                       for s in self.slots]
            nxt, self.cache = self._step(self.params, self.cache,
                                         jnp.asarray(tokens))
        return nxt, list(self.slots)

    def _plain_step(self, stand_down: str | None = None) -> list[int]:
        """One plain decode step's turn: take the step that is queued, or
        dispatch one; unless the rule (or the caller, naming its reason in
        ``stand_down``) stands down, park the rows that end at it by
        budget and dispatch the next step behind it; then fetch it, emit
        its rows' tokens and finish what finished."""
        done: list[int] = []
        self.decode_dispatches += 1
        self.decode_steps += 1
        queued, self._ahead = self._ahead, None
        with self._spans(_obs.BATCHER_DECODE_DISPATCH):
            nxt, rows = queued or self._dispatch_step()
            self._count_step_traffic(rows=rows)
            why = stand_down or self._stands_down(rows)
            if why is None:
                # device order: this step, the parking of the rows that end
                # at it (their writes in the next step meet the position
                # guard, as they would one turn later), the next step
                for i, s in enumerate(self.slots):
                    if s is not None and s.remaining == 1:
                        self._park_slot(i)
                        s.parked = True
                ahead, self.cache = self._step(self.params, self.cache, nxt)
                self._ahead = (ahead, [None if s is None or s.parked else s
                                       for s in self.slots])
                self.decode_ahead_dispatches += 1
            else:
                self.decode_ahead_standdowns[why] += 1
        with self._spans(_obs.BATCHER_DECODE_FETCH):
            nxt = self._fetch(nxt)
        with self._spans(_obs.BATCHER_EMIT):
            for i, s in enumerate(rows):
                if s is None:
                    continue
                tok = int(nxt[i])
                s.tokens.append(tok)
                self._emit_token(s.request_id, tok)
                s.remaining -= 1
                if s.remaining <= 0 or tok == self.eos_id:
                    done.append(s.request_id)
                    self._finish(i, s)
        return done

    def settle(self) -> list[int]:
        """Consume the step queued ahead, if there is one: fetch it, emit
        its tokens, finish what finishes — and dispatch no other — so that
        the slots and ``self.cache`` agree again.  Returns the ids it
        finished, as ``step()`` would have at its next call; a no-op
        (``[]``) when nothing is queued.

        For a caller that reads ``self.cache`` against the slots between
        two ``step()`` calls (a logit probe); nothing inside the package
        has to.  A queued step writes the rows it holds — the next
        position of each in the row's own leased pages, its counters and
        its recurrent state — and, of a free or parked row, the state
        alone (its K/V writes drop at the position guard; a row that ends
        by budget was parked ahead of it).  What may run while it is
        queued touches none of that: ``_admit`` and ``_admit_adopts``
        (hence ``adopt_session``) dispatch BEHIND it, into free slots'
        rows, whose state they replace whole, and into leased pages, free
        or shared prompt pages until then — pages released at a finish
        among them, which the step, the row parked, no longer writes — and
        ``_prefill`` consumes the step before it waits for its own tokens;
        a chunked admission streams into its leased pages and keeps its
        state beside the cache; the page traffic between turns
        (``export_prefix_cache``, hence ``serve_clone_request``, and
        ``import_prefix_cache``) reads indexed prompt pages and writes
        free pages, where no decode step writes.  ``unload_params`` (hence
        ``load_params``), ``set_role`` and a hot swap want an idle
        batcher, and none is queued without a seated row that goes on:
        ``run()`` ends, and the serve loop exits, with none queued;
        ``take_sessions`` drains a prefill-only batcher, which never
        decodes.  A queued step keeps the parameters it was dispatched
        with, as any step in flight does."""
        if self._ahead is None:
            return []
        return self._guarded(self._plain_step, stand_down="settle")

    def result(self, request_id: int, *, pop: bool = False) \
            -> np.ndarray | None:
        """Generated tokens of a FINISHED request (prompt excluded), or
        None while it is still pending/decoding — the non-blocking
        accessor for drivers that interleave ``step()`` with their own
        event loop instead of calling ``run()``.  ``pop=True`` releases
        the stored tokens, keeping a long-lived batcher's memory bounded
        by the in-flight set instead of every request ever served."""
        if pop:
            return self._results.pop(request_id, None)
        return self._results.get(request_id)

    def run(self) -> dict[int, np.ndarray]:
        """Drive ``step()`` until every submitted request has finished;
        returns ``{request_id: generated tokens}`` (prompt excluded)."""
        while self._pending or self._pending_adopt \
                or self._inflight is not None or any(self.slots):
            self.step()
        return dict(self._results)
