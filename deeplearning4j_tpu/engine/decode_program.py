"""DecodeProgram: the compiled half of continuous-batching decode,
over a PAGED KV virtual address space.

The serving sibling of StepProgram — one model's autoregressive
programs, compiled ONCE per shape and never again (the static-shape
constraint that makes one-program XLA serving work at all, per
"Automatic Full Compilation ... to Cloud TPUs", arXiv 1810.09868):

  decode step   ONE program per step width over the engine's fixed
                [max_slots] batch: consume each slot's current token
                (the host's, or where the host says so the one the
                step before emitted, which then never leaves the
                device before it is consumed: `step`'s `prev`/`take`)
                at its current LOGICAL position, scatter that
                position's K/V into a host-chosen (page, offset) write
                cell, gather each slot's attention window a whole page
                at a time through its [width] page ids in ring order
                (or, for a model that reads the pool itself, read each
                slot's live pages straight from it), attend under
                per-slot live masks, emit each slot's greedy next
                token. Requests joining/leaving, prefix pages being
                shared, copy-on-write forks, and ring wrap past
                max_ctx are all pure DATA (the host page table) — one
                compiled shape per step width, all compiled before
                traffic, so arbitrary traffic runs on the warm-up's
                compiles (pinned by trace counters).
  chunk prefill ONE program per window width, over `chunk_tokens`
                rows: process one aligned block of a prompt in
                parallel — causal within the chunk, attending to the
                prior context through the same gathered-page
                indirection — and park its K/V into the block's
                `chunk_pages` physical pages, or into scratch where
                the host says so. A prompt is a sequence of chunk
                dispatches interleaved between decode steps, so a long
                prompt never stalls resident generations, and a prompt
                whose prefix pages already live in the prefix trie
                skips the blocks they cover whole.
  page copy     the copy-on-write primitive: duplicate one physical
                page (all layers, K and V) inside the donated pool —
                what a slot pays to diverge from a shared page.

The window's WIDTH follows the live pages. A program's cost is linear
in the page ids it is handed (the gather, the zeroing of dead cells
and both cache contractions run over all of them), so a program
compiled for `pages_per_slot` ids pays for the whole window whatever
the slots hold. `widths` is a fixed ladder of widths in pages: powers
of two from the one that holds `WINDOW_FLOOR` positions up to
`pages_per_slot` (a model whose window is no longer than the floor has
a ladder of one). The host hands a program the narrowest ladder width
that holds the live pages — of the longest decoding slot for a step,
of the prior context for a chunk — and `step` / `prefill_chunk` pick
the compiled program by the width of the ids they are given. `warmup`
compiles and runs every width of both. A model that attends straight
from the pool (`decode_from_pool`, below) reads each slot's live pages
and no more, so its step's cost follows `live` and not the ids' width:
its step is compiled at `pages_per_slot` alone (`step_widths`, a
ladder of one), and only its chunk keeps the ladder.

What is the same for every model lives here: the three programs and
their compile-once keys, the page indirection (`window_pages`, scratch
page 0), the width ladder, donation, trace counters, the greedy token
and its finite verdict, and the named scopes of the work every model does (`embed`,
`kv_write`, `kv_read`, `head`, `kv_copy`). What a model is comes from
the model, and nothing here asks which one it is:

  kv_shape(n_pages, page_size), kv_dtype, kv_page_axis
        the pool: one buffer whose `kv_page_axis` is the physical page
        index, so one page id addresses every layer's cells of a page
        (one page-table entry per page, not per layer). GPT-2 keeps
        ``[L, 2, pages, page, H * D]`` float32, one row of whole
        128-lane tiles a token: with head_dim 64 minor the chip's
        compiler converted the whole pool in and out of every program
        (nn/attention.py, PERF.md PR 29); a latent-attention model one
        row a token, ``[L, pages, page, row]`` (nn/latent_attention.py).
  embed(params, tokens, positions) -> x
  project(lp, x, positions) -> (q, cell)
        a layer's query and what it caches of these positions
  write_cells(pool, li, cell, page, offset) -> pool
  read_window(pool, li, page_ids) -> window
        the scatter and the gather in the pool's own layout; `page`
        and `offset` are per slot in the decode step; a chunk hands
        over whole pages, `cell` as [pages, page_size, ...], `page` a
        page id a page and `offset` all of a page's cells (a slice),
        or, where it is one page long, that page and its offsets
  decode_finish(lp, x, q, window, live, active) -> (x, counts | None)
  chunk_finish(lp, x, q, cell, window, start) -> x
        attention over the gathered window and the layer's feed-forward
        half; `counts` is an int32 vector of `step_counters` (the
        model's names for it) over the `active` rows, summed here over
        layers and steps and read through `counters()`
  decode_from_pool(lp, x, q, pool, li, page_ids, live, active)
        -> (x, counts | None)
        in the place of `read_window` + `decode_finish` in the decode
        step, where the model has it: attention read straight from the
        pool, each `active` row's first ceil(live / page_size) page ids
        and nothing else (a kernel's read, under the `kv_read` scope),
        then the feed-forward half
  head(params, x) -> logits

A SECOND KIND OF STATE, for layers that keep no rows: a model may also
describe a state a slot, whatever the context (a linear-attention
layer's matrix a head and its short convolution's last inputs:
nn/delta_attention.py, zoo/hybrid_delta.py). It then gives

  mix_kind
        a name a layer, "pages" or "state". The pool holds the
        "pages" layers only: `kv_shape`'s layer count and the index
        `write_cells` / `read_window` take follow the model's own
        count of them, and `project` / `decode_finish` (or
        `decode_from_pool`) / `chunk_finish` are called for those
        layers alone.
  state_shape(max_slots), state_dtype
        the state: one buffer, or a dict of them, whose axes lead
        [state layer, slot, ...] (`init_state`; None for a model that
        describes none).
  state_step(lp, x, state, i, active, positions)
        -> (x, state, counts | None)
        the i-th state layer of the decode step, its feed-forward half
        included: each `active` row's own entry advanced by the row's
        token at its logical position, every other entry left as it
        was. No operation mixes slots, so the bitwise contract below
        holds for a state as it does for pages; the oracle carries a
        state of its own.
  state_chunk(lp, x, entry, n_state, positions) -> (x, entry)
        the same layer over a chunk of ONE slot at `positions` (start
        + 0 .. chunk_tokens - 1): `entry` is that slot's entry of the
        layer as the chunk found it, and comes back as it stands after
        the chunk's first `n_state` rows. A recurrence has no mask
        that hides a pad row, so the host says how many rows are
        absorbed (`state_rows`).

  A recurrence (nn/delta_attention.py, nn/short_conv.py) takes no
  position; a window of rows a slot (nn/window_attention.py's ring:
  position p in cell p mod window) takes them for its rotary and its
  mask.

Both programs then take the state after the pool and DONATE it like
the pool, and return it after it. The chunk program takes two more
scalars after `write_pages`: the `slot` whose entry it advances and
`n_state`; it reads the entry as `where(start == 0, 0, state[slot])`
— a chunk at position 0 starts its slot from zero, which is the only
reset there is (a select, so a poisoned slot's NaN does not survive
it) — and writes that slot's entry back. A model that describes no
state gets the programs it always had, argument for argument: no empty
buffer is threaded through them. What a state cannot do, the engine
turns off: serving/continuous.py says what (the prefix trie) and why.

Page 0 is SCRATCH: the write target for inactive/suppressed rows and
the gather target for pages with no live cell — never mapped live, and
its (possibly garbage) bytes are kept out inside the attention
primitives: a dead cell's score is masked, and what a sum would carry
of it is zeroed first (a read from the pool skips a page with no live
cell, and an empty slot's row reads none).

All three programs DONATE the pool (and the step and the chunk the
state, where there is one): updates are in-place, the caller
rebinds — program-lint's prog-unhonored-donation rule verifies the
executable alias map actually honors it (a silent copy of this buffer
per token is the regression the rule exists to catch; all three join
the --programs representative set).

Bitwise contract: the host passes each slot's page ids in RING order
(`window_pages`), so cell c = ring * page_size + offset of the
gathered window holds the position congruent to c modulo the window —
a function of the position alone. Before a wrap that is logical
token order; after one it is a rotation of it. Either way the engine
under any page-table history (shared prefixes, CoW forks, ring wrap,
eviction replay) presents the attention reduction with identical
operand values in identical order to the sequential oracle's — the
FP-associativity discipline that makes "bitwise equal to the oracle"
achievable at all. It holds at every width: no operation mixes
slots, a wider window only appends dead cells (masked, zeroed), and
engine and oracle agree bitwise when run at the same width. A chunk's
width is a function of its start alone, so the two always agree on
it; a step's is that of the longest slot decoding in it, so
`sequential_decode` takes a `width` to run a request's steps at the
one the engine ran them at. A step read from the pool has one width,
and reads no cell past a slot's live ones at any.

Forensics and policy ride the exact StepProgram rails: programs live
in the model's JitCache (record_trace inside traced bodies,
register_policy per key).
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


# The narrowest window a program is compiled for, in positions: the
# floor of the width ladder. Every width costs set-up time whether
# traffic uses it or not (a program to trace, lower, load and run
# once per layer), and a narrower window saves per position, so below
# some width a further program costs more set-up than its steps save.
# PERF.md (PR 33) has the measured cost of a program in `setup_s`.
WINDOW_FLOOR = 512

# The most tokens one prefill chunk holds. A chunk's LENGTH is a
# compute choice and a page's size a sharing grain: a chunk spans as
# many whole pages as this budget holds (at least one, so a page of
# this many tokens or more keeps the chunk it had). A dense product
# over this many rows is still bound by the weights it reads (GPT-2
# medium: 64 operations a byte at 128 rows against the v5e's 240), so
# the pages of a chunk cost about what one does. PERF.md (PR 37) has
# both lengths that were measured.
CHUNK_TOKENS = 128

# physical page 0: scratch — write sink for inactive/suppressed rows,
# gather target for dead cells (masked inside the attention kernels)
SCRATCH_PAGE = 0


class DecodeProgram:
    """One model's compiled chunk-prefill/decode/page-copy programs
    over a fixed slot batch and a fixed physical page pool.
    Holds NO request state — serving/continuous.py's DecodeEngine owns
    slots, the page table, the prefix trie, and refcounts; this class
    owns shapes, compilation, the pool layout, and the host-side
    window-page translation both the engine and the oracle share."""

    def __init__(self, model, max_slots: int = 8, page_size: int = 16,
                 n_pages: Optional[int] = None):
        if page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two "
                             f"(page-aligned pow2 blocks): {page_size}")
        if model.params is None:
            model.init()
        self.model = model
        self.max_slots = int(max_slots)
        self.page_size = int(min(page_size, model.max_ctx))
        # the attention window: every slot attends over at most
        # max_ctx logical positions (sliding once positions wrap)
        self.window = int(model.max_ctx)
        if self.window % self.page_size:
            raise ValueError(f"the window of {self.window} positions is "
                             f"not whole pages of {self.page_size}")
        self.pages_per_slot = self.window // self.page_size
        # a prefill chunk: the whole pages a budget of CHUNK_TOKENS
        # holds, never past the window
        self.chunk_pages = max(
            1, min(CHUNK_TOKENS, self.window) // self.page_size)
        self.chunk_tokens = self.chunk_pages * self.page_size
        # the ladder of window widths a program is compiled for, in
        # pages, ascending; the last is the whole window
        w = max(1, WINDOW_FLOOR // self.page_size)
        ladder = []
        while w < self.pages_per_slot:
            ladder.append(w)
            w *= 2
        self.widths: Tuple[int, ...] = (*ladder, self.pages_per_slot)
        # a model that attends straight from the pool reads each slot's
        # live pages whatever the ids' width: its step has one width
        self.from_pool = hasattr(model, "decode_from_pool")
        self.step_widths: Tuple[int, ...] = (
            (self.pages_per_slot,) if self.from_pool else self.widths)
        if n_pages is None:
            # equal HBM to a contiguous per-slot layout, + scratch
            n_pages = self.max_slots * self.pages_per_slot + 1
        self.n_pages = int(n_pages)
        if self.n_pages < self.pages_per_slot + 1:
            raise ValueError(
                f"n_pages {self.n_pages} cannot hold one slot's "
                f"window ({self.pages_per_slot} pages) + scratch")
        from deeplearning4j_tpu.nn.jit_cache import policy_name

        self.precision_policy = policy_name(
            getattr(model, "compute_dtype", None))
        # what each layer keeps (the model's `mix_kind`, a name a
        # layer; none: pages everywhere): a page layer's index into
        # the pool, counted over the page layers alone, or -1 - i for
        # the i-th state layer
        kinds = getattr(model, "mix_kind", None)
        self.has_state = kinds is not None and "state" in kinds
        self._layer_index: Tuple[int, ...] = ()
        if kinds is not None:
            n_page = n_state = 0
            for kind in kinds:
                if kind == "state":
                    n_state += 1
                    self._layer_index += (-n_state,)
                else:
                    self._layer_index += (n_page,)
                    n_page += 1
        # host-side dispatch tally per program kind and window width —
        # trace-counter siblings that count EXECUTIONS rather than
        # retraces, so the engine's stats (and the tracing story) can
        # report how many device dispatches a generation actually
        # cost, and how often each width was the one chosen
        self._dispatches = {"step": dict.fromkeys(self.step_widths, 0),
                            "chunk": dict.fromkeys(self.widths, 0),
                            "copy": 0}
        # the model's per-step counts (`step_counters`): totals on the
        # host, and the device vectors of steps not yet added to them
        self._counter_lock = threading.Lock()
        self._counter_totals = np.zeros(len(model.step_counters),
                                        np.int64)
        self._counter_pending: List = []

    # ---------------------------------------------------------- layout
    @property
    def kv_shape(self) -> Tuple[int, ...]:
        return tuple(self.model.kv_shape(self.n_pages, self.page_size))

    def init_kv(self):
        """The preallocated physical page pool (zeros; cells are
        zeroed in-kernel when dead and overwritten before they are
        readable otherwise)."""
        import jax.numpy as jnp

        return jnp.zeros(self.kv_shape, self.model.kv_dtype)

    def _layers(self, params):
        """(layer parameters, index) in order: a page layer's index
        into the pool, -1 - i for the i-th state layer."""
        layers = params["layers"]
        return zip(layers, self._layer_index or range(len(layers)))

    def init_state(self):
        """The per-slot state of a model that has one (zeros, in the
        model's own nesting; `state_dtype` is one dtype or a dtype a
        leaf), or None. A chunk at position 0 starts its slot from
        zero, so this is the only place the state is ever zeroed
        whole."""
        import jax.numpy as jnp

        if not self.has_state:
            return None
        shapes = self.model.state_shape(self.max_slots)
        dtypes = self.model.state_dtype
        if not isinstance(shapes, dict):
            return jnp.zeros(shapes, dtypes)
        return {k: jnp.zeros(shape, dtypes[k] if isinstance(dtypes, dict)
                             else dtypes) for k, shape in shapes.items()}

    def state_rows(self, prompt_len: int, start: int) -> int:
        """Rows of the chunk at `start` that a state absorbs: the
        prompt's tokens but the last, which the first-token step
        consumes (it runs at position prompt_len - 1; a state has no
        mask, so a token absorbed twice or a pad row absorbed once is
        a different state)."""
        return max(0, min(self.chunk_tokens, prompt_len - 1 - start))

    def chunk_starts(self, prompt_len: int,
                     from_token: int = 0) -> List[int]:
        """The chunk schedule for a prompt, a function of the position
        alone: the starts of the `chunk_tokens`-aligned blocks that
        hold a token at or after `from_token`, the first the prefix
        trie did not cover. A block the trie covers in part is run
        whole from its aligned start all the same (its covered pages'
        rows parked in scratch), so every cell of every page comes
        from the same row of the same program over the same split of
        prior window and own chunk, whoever fills it: the engine, a
        prefix twin or the oracle."""
        if prompt_len < 1:
            raise ValueError("prompt must carry at least one token")
        if prompt_len > self.window:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the attention "
                f"window {self.window}")
        b = self.chunk_tokens
        return list(range(int(from_token) // b * b, prompt_len, b))

    def block_pages(self, prompt_len: int, start: int) -> range:
        """The logical pages of the block at `start` that hold a
        prompt token: the pages its chunk fills (a block's later pages
        are past the prompt's end, and their rows go to scratch)."""
        ps = self.page_size
        end = min(start + self.chunk_tokens, prompt_len)
        return range(start // ps, -(-end // ps))

    def live_pages(self, pos: int) -> int:
        """Pages of a slot's window that hold a live cell at logical
        position `pos` (every one after a wrap)."""
        return -(-min(pos + 1, self.window) // self.page_size)

    def width_for(self, n_pages: int, widths=None) -> int:
        """The narrowest width of `widths` (the ladder's by default)
        that holds `n_pages` pages."""
        for w in widths or self.widths:
            if w >= n_pages:
                return w
        raise ValueError(f"{n_pages} pages exceed the window's "
                         f"{self.pages_per_slot}")

    def step_width_for(self, n_pages: int) -> int:
        """The narrowest step width that holds `n_pages` pages."""
        return self.width_for(n_pages, self.step_widths)

    def window_pages(self, table: Sequence[Optional[int]], pos: int,
                     width: Optional[int] = None) -> np.ndarray:
        """Host-side virtual→physical translation: the [width] page
        ids of one slot's attention window at logical position `pos`
        (`width` defaults to the narrowest ladder width that holds the
        live pages; after a wrap every page is live and that is
        `pages_per_slot`), in RING order — cell
        c = ring * page_size + offset of the gathered window holds the
        position q with q % window == c, so the live cells are
        c < live = min(pos + 1, window): logical token order until the
        ring wraps, a rotation of it (every cell live) after. Ring
        entries with no live cell point at the scratch page. `table`
        is the slot's ring page table (pages_per_slot entries);
        entries for live positions must be mapped. Shared by the
        engine and the sequential oracle — the single definition of
        reduction order the bitwise contract rests on."""
        n = self.live_pages(pos)
        if width is None:
            width = self.width_for(n)
        elif width < n:
            raise ValueError(f"a window of {width} pages cannot hold "
                             f"the {n} live at position {pos}")
        page_ids = np.full(width, SCRATCH_PAGE, np.int32)
        page_ids[:n] = table[:n]
        return page_ids

    # ------------------------------------------------------- compile
    def _width(self, width: Optional[int], widths=None) -> int:
        """`width` as one of `widths`, the ladder's by default (None:
        the whole window). A program of any other width would compile
        under traffic."""
        widths = widths or self.widths
        if width is None:
            return self.pages_per_slot
        if width not in widths:
            raise ValueError(f"window width {width} is not one of "
                             f"{widths}")
        return int(width)

    def decode_key(self, width: Optional[int] = None):
        return ("decode_step", self.max_slots, self.window,
                self.n_pages, self._width(width, self.step_widths))

    def chunk_key(self, width: Optional[int] = None):
        return ("decode_chunk_prefill", self.chunk_tokens,
                self.page_size, self.window, self.n_pages,
                self._width(width))

    def copy_key(self):
        return ("decode_page_copy", self.n_pages)

    def _program(self, key, builder):
        cache = self.model._jit_cache
        if key not in cache:
            cache[key] = builder(str(key))
            cache.register_policy(key, self.precision_policy)
        return cache[key]

    def _decode_program(self, width: Optional[int] = None):
        return self._program(self.decode_key(width), self._build_decode)

    def _chunk_program(self, width: Optional[int] = None):
        return self._program(self.chunk_key(width), self._build_chunk)

    def _copy_program(self):
        return self._program(self.copy_key(), self._build_copy)

    def _build_decode(self, trace_key: str):
        """Compile the shared decode step (the window's width is the
        page ids': one jitted function a step width, each traced for
        one shape). Per-slot independence is the load-bearing
        property, at every width: no op mixes slots (batched einsums,
        per-row norms/softmax, per-row gathers), so an active slot's
        emitted token is a function of ITS cells alone — the
        byte-identity-under-churn contract tests/test_decode.py pins
        against the sequential oracle."""
        import jax
        import jax.numpy as jnp

        model = self.model
        cache = model._jit_cache

        def body(params, pool, state, tokens, positions, page_ids,
                 write_page, write_off, prev, take):
            cache.record_trace(trace_key)
            # The named scopes say what the work is, with no layer
            # index (a reader sums over layers): they are what the
            # device trace's operations are summed by
            # (benchmark/timeline.py), so they outlive a change to how
            # the work is done. The model's functions name their own
            # (`qkv`, `attn`, `mlp`, ...).
            with jax.named_scope("embed"):
                # a row the host marks takes the token the step before
                # this one emitted for it, which never left the device
                tokens = jnp.where(take, prev, tokens)
                x = model.embed(params, tokens, positions)
            live = jnp.minimum(positions + 1, self.window)
            # a row whose window maps no page is an empty slot: its
            # garbage is not counted, and its state is not advanced
            active = page_ids[:, 0] != SCRATCH_PAGE
            counts = []
            for lp, li in self._layers(params):
                if li < 0:
                    # a state layer: each active row's own entry
                    # advanced, the others left as they were
                    x, state, c = model.state_step(lp, x, state, -1 - li,
                                                   active, positions)
                    if c is not None:
                        counts.append(c)
                    continue
                q, cell = model.project(lp, x, positions)
                # scatter: the write cell is host-chosen (suppressed
                # rows target scratch), advanced indices broadcast per
                # slot
                with jax.named_scope("kv_write"):
                    pool = model.write_cells(pool, li, cell, write_page,
                                             write_off)
                if self.from_pool:
                    # each active slot's live pages, read by the model
                    # straight from the pool (its read is `kv_read`)
                    x, c = model.decode_from_pool(lp, x, q, pool, li,
                                                  page_ids, live, active)
                else:
                    # gather: each slot's window a whole page at a time
                    # in ring order — the virtual-memory read
                    with jax.named_scope("kv_read"):
                        window = model.read_window(pool, li, page_ids)
                    x, c = model.decode_finish(lp, x, q, window, live,
                                               active)
                if c is not None:
                    counts.append(c)
            with jax.named_scope("head"):
                logits = model.head(params, x)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # per-slot finite-logits verdict (the NonFiniteGuard
                # discipline applied to serving): ONE fused reduction
                # over the logits the step already materialized, so
                # slot health rides the same dispatch — a False row
                # means this slot's numerics are poison and its emitted
                # token must not be trusted (DecodeEngine quarantines
                # the slot AND its private pages, purges its trie
                # entries, and replays the request on a healthy slot)
                ok = jnp.all(jnp.isfinite(logits), axis=-1)
            out = (pool,) if state is None else (pool, state)
            if counts:
                return (*out, nxt, ok, sum(counts))
            return (*out, nxt, ok)

        if self.has_state:
            def decode_fn(params, pool, state, tokens, positions,
                          page_ids, write_page, write_off, prev, take):
                return body(params, pool, state, tokens, positions,
                            page_ids, write_page, write_off, prev, take)

            return jax.jit(decode_fn, donate_argnums=(1, 2))

        def decode_fn(params, pool, tokens, positions, page_ids,
                      write_page, write_off, prev, take):
            return body(params, pool, None, tokens, positions, page_ids,
                        write_page, write_off, prev, take)

        return jax.jit(decode_fn, donate_argnums=(1,))

    def _build_chunk(self, trace_key: str):
        """Compile the chunk-prefill program: one `chunk_tokens`
        block of a prompt, causal within the chunk, prior context via
        gathered pages, row r's K/V parked at
        `(write_pages[r // page_size], r % page_size)` (`write_pages`
        is traced — no recompile per page; an entry that is the
        scratch page throws its rows away: a page the prefix trie
        already holds, or one past the prompt's end). Pad rows in the
        prompt's last page write cells the live masks never expose;
        they are overwritten cell-by-cell as decoding advances. ONE
        length a width: a short chunk is padded, since the weights set
        a chunk's time and every further program costs set-up. A chunk
        of several pages parks a page at a time: a row at a time the
        128 rows of GPT-2's chunk were 1.07 of its 2.43 ms on the chip
        (each row an update of its own), a page at a time 0.16
        (PERF.md, PR 37)."""
        import jax
        import jax.numpy as jnp

        model = self.model
        t = self.chunk_tokens
        cache = model._jit_cache
        cp, ps = self.chunk_pages, self.page_size

        def park(pool, li, cell, write_pages):
            if cp > 1:
                # one update a page, each a whole page's rows
                paged = jax.tree.map(
                    lambda a: jnp.reshape(a, (cp, ps) + a.shape[1:]), cell)
                return model.write_cells(pool, li, paged, write_pages,
                                         slice(None))
            # a chunk of one page: that page and its offsets, the
            # program the page-128 cells always had
            return model.write_cells(pool, li, cell, write_pages[0],
                                     np.arange(ps))

        def body(params, pool, state, tokens, start, page_ids,
                 write_pages, slot, n_state):
            cache.record_trace(trace_key)
            positions = start + jnp.arange(t)
            with jax.named_scope("embed"):
                x = model.embed(params, tokens, positions)
            if state is not None:
                # the slot's entry of every state layer; a chunk at 0
                # starts from zero whatever the slot held (a select:
                # a poisoned slot's NaN does not survive the reset)
                entries = jax.tree.map(
                    lambda a: jnp.where(
                        start == 0, 0, jax.lax.dynamic_index_in_dim(
                            a, slot, 1, keepdims=False)), state)
                after = []
            for lp, li in self._layers(params):
                if li < 0:
                    x, entry = model.state_chunk(
                        lp, x, jax.tree.map(lambda a: a[-1 - li], entries),
                        n_state, positions)
                    after.append(entry)
                    continue
                # project + PARK the chunk's cells before gathering the
                # prior ones — the same scatter-then-gather order as
                # the decode step, which is what lets XLA update the
                # donated pool in place (a gather of the PRE-scatter
                # pool forced two full-pool copies). Safe because the
                # prior pages can never alias a page written here:
                # prefill never wraps (prompt <= window), so the page
                # ids name earlier blocks' pages, or scratch, whose
                # cells are dead: masked, and zeroed where a sum would
                # carry them.
                q, cell = model.project(lp, x, positions)
                with jax.named_scope("kv_write"):
                    pool = park(pool, li, cell, write_pages)
                with jax.named_scope("kv_read"):
                    window = model.read_window(pool, li, page_ids)
                x = model.chunk_finish(lp, x, q, cell, window, start)
            if state is None:
                return pool
            state = jax.tree.map(
                lambda a, *es: jax.lax.dynamic_update_index_in_dim(
                    a, jnp.stack(es).astype(a.dtype), slot, 1),
                state, *after)
            return pool, state

        if self.has_state:
            def chunk_fn(params, pool, state, tokens, start, page_ids,
                         write_pages, slot, n_state):
                return body(params, pool, state, tokens, start, page_ids,
                            write_pages, slot, n_state)

            return jax.jit(chunk_fn, donate_argnums=(1, 2))

        def chunk_fn(params, pool, tokens, start, page_ids, write_pages):
            return body(params, pool, None, tokens, start, page_ids,
                        write_pages, None, None)

        return jax.jit(chunk_fn, donate_argnums=(1,))

    def _build_copy(self, trace_key: str):
        """Compile the copy-on-write primitive: duplicate one physical
        page (every layer's cells of it) inside the donated pool."""
        import jax

        cache = self.model._jit_cache
        axis = self.model.kv_page_axis
        shape = list(self.kv_shape)
        shape[axis] = 1

        def at(page):
            return tuple(page if i == axis else 0
                         for i in range(len(shape)))

        def copy_fn(pool, src, dst):
            cache.record_trace(trace_key)
            with jax.named_scope("kv_copy"):
                page = jax.lax.dynamic_slice(pool, at(src), shape)
                return jax.lax.dynamic_update_slice(pool, page, at(dst))

        return jax.jit(copy_fn, donate_argnums=(0,))

    # ----------------------------------------------------------- run
    def step(self, kv, tokens, positions, page_ids, write_page,
             write_off, state=None, prev=None, take=None):
        """One decode step over all slots. `tokens`/`positions`/
        `write_page`/`write_off` are host [max_slots] int arrays and
        `page_ids` a host [max_slots, width] int array (one
        `window_pages` row per slot, all of one step width that holds
        the longest slot's live pages: the engine's translated page
        table), which picks the program; returns
        (new_kv, next_tokens, finite_ok) with `kv` donated — the
        caller MUST rebind. `finite_ok` is the per-slot finite-logits
        verdict ([max_slots] bool): a False row's token is numeric
        poison. Inactive/suppressed rows write scratch and gather
        scratch pages (zeroed in-kernel) — the host decides whose
        outputs are real. A model with state takes `state` too,
        donated like `kv`, and the result is (new_kv, next_tokens,
        finite_ok, new_state): every row whose window maps a page had
        its own entry advanced by its token, write suppressed or
        not.

        `prev` is an earlier step's `next_tokens`, still on the device
        and not donated (its caller may not have fetched it yet), and
        `take` a host [max_slots] bool: a row it marks consumes
        `prev`'s token in place of `tokens`', so a caller can dispatch
        a step before it has read the one before (DecodeEngine's
        run-ahead). With neither every row takes the host's.

        The host arrays go over as numpy copies: an argument made with
        `jnp.asarray` is a device program of its own between two steps
        (`jit_convert_element_type` in the trace, PERF.md PR 28), and a
        copy is the caller's to change again at once.

        A step read from the pool stops at each row's live pages, so
        ids of a narrower window are the same ids with scratch after
        them: they are padded to its one width."""
        width = np.shape(page_ids)[1]
        if self.from_pool and width < self.pages_per_slot:
            page_ids = np.pad(page_ids,
                              ((0, 0), (0, self.pages_per_slot - width)),
                              constant_values=SCRATCH_PAGE)
            width = self.pages_per_slot
        fn = self._decode_program(width)
        self._dispatches["step"][width] += 1
        held = (kv,) if state is None else (kv, state)
        if prev is None:
            prev = take = np.zeros(self.max_slots, np.int32)
        out = fn(self.model.params, *held,
                 *(np.array(a, np.int32) for a in (
                     tokens, positions, page_ids, write_page, write_off)),
                 prev, np.array(take, bool))
        kv, *state = out[:len(held)]
        nxt, ok, *counts = out[len(held):]
        if counts:
            self._note_counts(counts[0])
        return (kv, nxt, ok, *state)

    def _note_counts(self, counts) -> None:
        """The step's counts ride its own fetch: their copy to the host
        starts with the dispatch, and they are added to the totals two
        steps late. A caller runs at most one step ahead of its fetch,
        so it has had that step's tokens by then and the add waits for
        nothing."""
        counts.copy_to_host_async()
        with self._counter_lock:
            self._counter_pending.append(counts)
            self._add_counts(keep=2)

    def _add_counts(self, keep: int) -> None:
        while len(self._counter_pending) > keep:
            self._counter_totals += np.asarray(
                self._counter_pending.pop(0))

    def counters(self, wait: bool = True) -> Dict[str, int]:
        """The model's `step_counters`, summed over every decode step
        dispatched so far (waits for the newest if it still runs), or
        with `wait` False over all but the newest, which a caller that
        has one step in flight leaves out so as not to block on it."""
        with self._counter_lock:
            self._add_counts(keep=0 if wait else 1)
            return {k: int(v) for k, v in zip(self.model.step_counters,
                                              self._counter_totals)}

    def prefill_chunk(self, kv, chunk: Sequence[int], start: int,
                      page_ids, write_pages, state=None,
                      slot: int = 0, n_state: int = 0):
        """Prefill one prompt chunk (positions
        start..start+len(chunk)-1 of a `chunk_tokens`-aligned block,
        padded to `chunk_tokens`) into the physical pages
        `write_pages`, one a page of the block in order (at most
        `chunk_pages`; the scratch page throws a page's rows away, as
        do the pages past those given), attending to the prior context
        through `page_ids` (`window_pages(table, start - 1)`: ids of a
        ladder width that holds the `start / page_size` prior pages,
        cells >= start dead), whose width picks the program. `kv` is
        donated — rebind. A model with state takes `state` (donated;
        the result is then (new_kv, new_state)), the `slot` whose
        entry the chunk advances — read as zero where `start` is 0 —
        and `n_state`, the rows that entry absorbs (`state_rows`)."""
        chunk = np.asarray(chunk, np.int32).ravel()
        padded = np.zeros(self.chunk_tokens, np.int32)
        padded[:len(chunk)] = chunk
        write_pages = np.asarray(write_pages, np.int32).ravel()
        pages = np.full(self.chunk_pages, SCRATCH_PAGE, np.int32)
        pages[:len(write_pages)] = write_pages
        width = np.shape(page_ids)[0]
        fn = self._chunk_program(width)
        self._dispatches["chunk"][width] += 1
        # numpy values, as `step`'s: no argument is a device program
        args = (padded, np.int32(start), np.array(page_ids, np.int32),
                pages)
        if state is None:
            return fn(self.model.params, kv, *args)
        return fn(self.model.params, kv, state, *args, np.int32(slot),
                  np.int32(n_state))

    def copy_page(self, kv, src: int, dst: int):
        """Copy-on-write: duplicate physical page `src` into `dst`
        (every layer's cells of it). `kv` is donated — rebind."""
        fn = self._copy_program()
        self._dispatches["copy"] += 1
        return fn(kv, np.int32(src), np.int32(dst))

    def warmup(self, kv, buckets: Sequence[int] = (), state=None):
        """Compile every program up front, the chunk at every ladder
        width and the step at every step width (serving warmup
        discipline: compiles happen
        before traffic, the trace counters pin that none happen
        after). `buckets` is accepted for call-site compatibility and
        ignored — chunked prefill replaced the per-bucket prefill
        family with ONE chunk shape a width. Returns the
        (donated-through) pool buffer, and the state's after it
        where the model has one (made here if none is given; its
        programs run with it: no row is active and the chunk absorbs
        none, so a fresh state comes back as it went in)."""
        del buckets
        if self.has_state and state is None:
            state = self.init_state()
        kv = self.copy_page(kv, SCRATCH_PAGE, SCRATCH_PAGE)
        s = self.max_slots
        zs = np.zeros(s, np.int32)
        for w in self.widths:
            out = self.prefill_chunk(kv, [0] * self.chunk_tokens, 0,
                                     np.full(w, SCRATCH_PAGE, np.int32),
                                     (), state=state)
            kv, state = out if self.has_state else (out, None)
            if w not in self.step_widths:
                continue
            kv, _, _, *rest = self.step(
                kv, zs, zs, np.full((s, w), SCRATCH_PAGE, np.int32),
                zs, zs, *(() if state is None else (state,)))
            state = rest[0] if rest else None
        return (kv, state) if self.has_state else kv

    def trace_stats(self) -> dict:
        """`dispatches` counts executions by program, and the chunk's
        and the step's by window width in pages beside their sums."""
        cache = self.model._jit_cache
        d = self._dispatches
        return {"trace_counts": cache.trace_counts(),
                "total_traces": cache.total_traces(),
                "compiles_total": cache.compiles_total(),
                "compile_events": cache.compile_events(),
                "dispatches": {"step": sum(d["step"].values()),
                               "chunk": sum(d["chunk"].values()),
                               "copy": d["copy"],
                               "step_by_width": dict(d["step"]),
                               "chunk_by_width": dict(d["chunk"])}}

    # ------------------------------------------------------------ lint
    def lint_records(self, buckets: Sequence[int] = ()) -> List:
        """ProgramRecords for the decode step and the chunk prefill at
        the narrowest and the widest ladder width (one record each
        where the ladder is one width, and one step where the step has
        one width; the widest keeps the bare name, a narrower one adds
        `_w<pages>`), and the page copy — built
        through the same cache paths the engine uses (policy
        registered), traced/lowered by the lint but never executed.
        Donation on the page pool is DECLARED on every record
        (donate_argnums) so prog-unhonored-donation verifies the
        executable alias map genuinely aliases the pool in place — a
        silently-copied pool would double decode memory AND pay a
        full-pool copy per token/chunk."""
        del buckets
        import jax.numpy as jnp

        from deeplearning4j_tpu.analysis.program_lint import (
            ProgramRecord,
        )

        model = self.model
        kv = self.init_kv()
        # a model with state: the state rides beside the pool in both
        # programs, donated and checked like it
        held = (kv, self.init_state()) if self.has_state else (kv,)
        donated = tuple(range(1, 1 + len(held)))
        tail = (jnp.int32(0), jnp.int32(0)) if self.has_state else ()
        s = self.max_slots
        source = "deeplearning4j_tpu/engine/decode_program.py"
        zs = jnp.zeros(s, jnp.int32)
        copy_fn = self._copy_program()
        records = []
        for w in sorted({self.widths[0], self.widths[-1]}):
            tag = "" if w == self.pages_per_slot else f"_w{w}"
            chunk_fn = self._chunk_program(w)
            if w in self.step_widths:
                step_fn = self._decode_program(w)
                records.append(ProgramRecord(
                    name=f"decode_step_s{s}{tag}",
                    fn=getattr(step_fn, "__wrapped__", step_fn),
                    example_args=(model.params, *held, zs, zs,
                                  jnp.zeros((s, w), jnp.int32), zs, zs,
                                  zs, jnp.zeros(s, bool)),
                    donate_argnums=donated,
                    precision_policy=self.precision_policy,
                    source=source,
                    consumed_outputs=tuple(range(
                        2 + len(held) + bool(model.step_counters)))))
            records += [
                ProgramRecord(
                    name=f"decode_prefill_c{self.chunk_tokens}{tag}",
                    fn=getattr(chunk_fn, "__wrapped__", chunk_fn),
                    example_args=(model.params, *held,
                                  jnp.zeros(self.chunk_tokens, jnp.int32),
                                  jnp.int32(0), jnp.zeros(w, jnp.int32),
                                  jnp.ones(self.chunk_pages, jnp.int32),
                                  *tail),
                    donate_argnums=donated,
                    precision_policy=self.precision_policy,
                    source=source,
                    consumed_outputs=tuple(range(len(held))))]
        return records + [
            ProgramRecord(
                name="decode_page_copy",
                fn=getattr(copy_fn, "__wrapped__", copy_fn),
                example_args=(kv, jnp.int32(1), jnp.int32(2)),
                donate_argnums=(0,),
                precision_policy=self.precision_policy, source=source,
                consumed_outputs=(0,)),
        ]
