"""The contract between a model's core and the serving engine.

``inference.ContinuousBatchingEngine`` (and ``generation.generate_paged``)
serve whatever subclasses ``ServingCore`` and use of it exactly what is
written here: facts the core DECLARES, the cache it needs, and programs of
FIXED arity in and out. Nothing is probed: a core without state beside its
pages hands over an EMPTY pytree for it (which adds no input to a compiled
program), a core without counters hands back None for them, and an optional
program is named in ``optional_programs``, not found by ``hasattr``. A new
family is a subclass of this and its cache, not an edit of the engine.
"""

import jax.numpy as jnp


class ServingCore:
    # what ``serving::prefill`` says of the pages: "gqa" (K and V pages per
    # KV head, all that the KV-page handoff carries), "mla", "cca", "hybrid"
    attention_kind = "gqa"
    # names of what ``decode_step_paged`` counts on the device every tick
    tick_counters = ()
    # which of ``prefill_chunk_paged`` (chunked prefill, a prefix hit's
    # suffix) and ``decode_verify_paged`` (speculative decoding, a full
    # prefix hit) the core implements, by name
    optional_programs = ()

    def pool_layers(self):
        """The layers that keep pages, in order: each has
        ``alloc_pool(num_pages, page_size)``."""
        return [layer.self_attn for layer in self.layers]

    def alloc_paged_caches(self, batch: int, max_len: int,
                           page_size: int = 128):
        """(pools, tables): one pool entry (arrays [heads, pages, page,
        width]) for each of ``pool_layers()``, possibly none, and the block
        table [batch, pages a sequence], pages assigned contiguously (at
        serving scale the allocator is the engine's)."""
        pages_per_seq = -(-max_len // page_size)
        num_pages = batch * pages_per_seq
        pools = [layer.alloc_pool(num_pages, page_size)
                 for layer in self.pool_layers()]
        return pools, jnp.arange(num_pages, dtype=jnp.int32).reshape(
            batch, pages_per_seq)

    def alloc_slot_state(self, slots: int):
        """What a sequence carries from token to token OUTSIDE its pages: a
        pytree whose leaves lead with the slot; empty for a core whose
        state is all in its pages."""
        return ()

    def prefill_paged(self, input_ids, pools, tables, slot_state, slot,
                      last_idx):
        """The prompt of ONE sequence, ``input_ids`` [1, bucket] ->
        (hidden [1, bucket, d], pools, slot_state): its pages written
        through ``tables`` [1, pages], slot ``slot`` of the state set from
        the prompt's true last position ``last_idx``."""
        raise NotImplementedError

    def decode_step_paged(self, token_ids, pos, pools, tables, slot_state):
        """One token of every row, ``token_ids`` [b] at ``pos`` [b] ->
        (hidden [b, 1, d], pools, slot_state, counts): row i of the state
        is sequence i's; ``counts`` is the tick's ``tick_counters`` as an
        int32 vector, None for a core that declares none."""
        raise NotImplementedError

    def prefill_chunk_paged(self, input_ids, offset, pools, tables):
        """Optional. ``input_ids`` [1, T] at positions ``offset``.. over the
        pages already written -> (hidden, pools)."""
        raise NotImplementedError

    def decode_verify_paged(self, token_ids, pos, pools, tables):
        """Optional. ``token_ids`` [b, T] at per-row positions ``pos[b]``..
        -> (hidden [b, T, d], pools)."""
        raise NotImplementedError

    # what ``build_log`` says of a program, None where there is nothing to

    def expert_path(self, rows: int):
        """(path, rows of a step) by which routed experts run ``rows``."""
        return None

    def state_path(self, rows, slots: int):
        """The form a recurrence takes in a prefill of ``rows`` positions
        or, ``rows`` None, in a tick of ``slots`` slots."""
        return None
