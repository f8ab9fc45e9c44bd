"""PagedKVManager: glue between the page pool / radix tree and the engine.

Owns the host-side accounting for the engine's device page pool and the
serving-path policy around it:

- ``match``   — at admission, find the longest stored token prefix of the
  request's (fully retokenized) conversation and the pages covering it,
  retaining them for the lane on the spot (the scheduler runs the adopt
  copy a tick later; unpinned pages could be evicted and reallocated to
  another sequence in that gap);
- ``adopt``   — copy the matched pages into the admitted lane's slab
  (one bucketed device gather);
- ``publish`` — at finish, store the lane's fed tokens' whole pages back
  into the pool, deduplicating against the tree so a prefix two streams
  share is physically stored ONCE (the second publisher allocates pages
  only for its unshared suffix, forking copy-on-write at a mid-page
  divergence);
- ``release_lane`` / ``reset`` — refcount hygiene and the error path.

All engine calls are made by the scheduler thread; ``lock`` only protects
the host-side accounting against concurrent /v1/debug/kv and /metrics
readers.
"""

from __future__ import annotations

import logging
from typing import Any

from ..analysis.lockwatch import make_lock
from ..obs.metrics import get_registry
from ..obs.recorder import get_recorder
from ..obs.spans import get_span_tracker
from ..runtime.faults import InjectedFault, get_fault_plane
from .pool import PagePool
from .radix import RadixTree

logger = logging.getLogger(__name__)

DEFAULT_PAGE_SIZE = 16


class PagedKVManager:
    def __init__(
        self,
        engine: Any,
        page_size: int = 0,
        n_pages: int = 0,
        evict_counter: Any = None,
        native: bool = False,
    ) -> None:
        self.engine = engine
        self.page_size = page_size or DEFAULT_PAGE_SIZE
        # native mode (ISSUE 16): the pool is the lanes' only KV home —
        # adopt becomes refcount bumps + a page-table write (device copy
        # only for a COW mid-page boundary) and publish becomes ownership
        # transfer of pages the lane already wrote
        self.native = bool(native)
        n = engine.init_kv_pool(self.page_size, n_pages, native=self.native)
        self.recorder = get_recorder()
        # the component="kv" span over the radix match (adopt and publish
        # are the scheduler's spans, their device copies the engine's)
        self.spans = get_span_tracker()
        self.pool = PagePool(n, self.page_size, on_event=self._pool_event)
        self.tree = RadixTree(self.page_size)
        self.lock = make_lock("kv.manager")
        self._lane_pages: dict[int, list[int]] = {}
        self._lane_match_tokens: dict[int, int] = {}
        # radix anchor of each lane's last match (runtime/spec.py shared
        # n-gram store): (node_id, matched token count), or absent when
        # the match found nothing
        self._lane_anchor: dict[int, tuple[int, int]] = {}
        # dashboards keep their dllama_cache_evictions_total series: the
        # ApiState hands us its handle and radix evictions feed it
        self._evict_counter = evict_counter
        obs = get_registry()
        self.g_total = obs.gauge(
            "dllama_kv_pages_total",
            "Usable pages in the shared KV pool (excludes the scratch page).",
        )
        self.g_free = obs.gauge(
            "dllama_kv_pages_free", "KV pool pages on the free list."
        )
        self.g_shared = obs.gauge(
            "dllama_kv_pages_shared",
            "KV pool pages referenced by the radix tree AND at least one "
            "live lane (refcount >= 2) — the physically-shared prefix "
            "storage.",
        )
        self.c_hits = obs.counter(
            "dllama_radix_hits_total",
            "Admissions whose conversation matched a stored radix prefix "
            "and adopted shared pages.",
        )
        self.c_evictions = obs.counter(
            "dllama_radix_evictions_total",
            "Pages LRU-evicted from radix-tree leaves to make room for a "
            "publish.",
        )
        self.c_shared_tokens = obs.counter(
            "dllama_shared_prefix_tokens_total",
            "Prompt tokens served from shared pool pages instead of being "
            "re-prefilled (sum of adopted prefix lengths).",
        )
        self.c_cow = obs.counter(
            "dllama_kv_cow_forks_total",
            "Copy-on-write page forks: a publish diverged mid-page from a "
            "stored prefix and took a private copy of that page slot.",
        )
        self.g_total.set(n - 1)
        self._update_gauges_locked()

    # -- internals ---------------------------------------------------------
    def _pool_event(self, kind: str, payload: dict) -> None:
        self.recorder.record(kind, **payload)
        if kind == "kv_cow_fork":
            self.c_cow.inc()

    def _update_gauges_locked(self) -> None:
        st = self.pool.stats()
        self.g_free.set(st.free)
        self.g_shared.set(st.shared)

    # -- admission ---------------------------------------------------------
    def match(self, lane: int, tokens: list[int]) -> tuple[int, list[int]]:
        """Longest reusable stored prefix of ``tokens``: returns
        ``(n_reused_tokens, pages)``. Reuse is capped one short of the
        prompt (the engine must be fed at least one token) and to the
        rows the collected pages actually cover; a partial final page is
        fine (its stale tail rows are overwritten by suffix prefill
        before any query can attend to them).

        The returned pages are retained for ``lane`` HERE, inside the
        lock: the scheduler runs the adopt copy one tick later, and
        another lane's publish->evict in that window could otherwise
        free and reallocate refcount-1 pages, silently handing the new
        lane a different sequence's KV. Every admission-failure path
        already funnels through :meth:`release_lane`, which drops the
        retain whether or not the adopt copy ever ran."""
        ps = self.page_size
        with self.spans.span(
            "kv_match", component="kv", lane=lane, n_tokens=len(tokens)
        ), self.lock:
            # a lane admitted twice without release would leak a retain
            stale = self._lane_pages.pop(lane, None)
            if stale:
                self.pool.release(stale)
            mr = self.tree.match(tokens)
            # the anchor follows the raw token match (not the page cap):
            # sibling grouping only needs prefix identity, not adoptable
            # KV — a lane can share an anchor with zero reusable pages
            if mr.anchor is not None:
                self._lane_anchor[lane] = (mr.anchor, mr.n_tokens)
            else:
                self._lane_anchor.pop(lane, None)
            m = min(mr.n_tokens, len(mr.pages) * ps, len(tokens) - 1)
            if m <= 0:
                self._lane_match_tokens[lane] = 0
                self._update_gauges_locked()
                return 0, []
            n_pages = -(-m // ps)  # ceil
            pages = mr.pages[:n_pages]
            self.pool.retain(pages)
            self._lane_pages[lane] = list(pages)
            self._lane_match_tokens[lane] = m
            self._update_gauges_locked()
            return m, pages

    def adopt(self, lane: int, pages: list[int]) -> None:
        """Slab mode: device-copy ``pages`` (already retained by
        :meth:`match`) into ``lane``'s slab. Native mode: build the lane's
        full page list — the shared prefix pages as-is, a COW fork of a
        mid-page boundary (the only device copy), and freshly allocated
        private pages for everything the lane will write — and point the
        engine's page table at it. A full-page prefix match therefore
        moves ZERO device bytes."""
        if not self.native:
            if pages:
                self.engine.kv_adopt(lane, pages)
            return
        self._adopt_native(lane, pages)

    def _adopt_native(self, lane: int, pages: list[int]) -> None:
        ps = self.page_size
        n_blocks = self.engine._kv_n_blocks
        with self.lock:
            fault = get_fault_plane().draw("kv_alloc", op="adopt")
            if fault is not None:
                raise fault
            m = self._lane_match_tokens.get(lane, 0)
            lane_list = list(pages)
            if m % ps and lane_list:
                # mid-page boundary: the lane will scatter rows >= m into
                # this slot, so it needs a private copy of the shared page
                orig = lane_list[-1]
                fork = self._alloc_lane_pages(1, lane, fork_src=orig)[0]
                try:
                    self.engine.kv_page_copy([orig], [fork])
                except BaseException:
                    self.pool.release([fork])
                    raise
                # swap the lane's retain from the shared original to the
                # private fork (the tree keeps its own ref on the original)
                self.pool.release([orig])
                lane_list[-1] = fork
            need = n_blocks - len(lane_list)
            if need > 0:
                lane_list += self._alloc_lane_pages(need, lane)
            self._lane_pages[lane] = lane_list
            self.engine.adopt_pages(lane, lane_list)
            self._update_gauges_locked()

    def _alloc_lane_pages(
        self, n: int, lane: int, fork_src: int | None = None
    ) -> list[int]:
        """Allocate ``n`` private pages for a native lane, LRU-evicting
        refcount-1 tree leaves under pressure. Unlike the publish path a
        shortfall here RAISES (MemoryError): admission cannot proceed
        without somewhere to write, and the scheduler's retry/fail path
        already handles a transient adopt failure."""
        short = n - self.pool.free_pages
        if short > 0:
            freed = self.tree.evict(short, self.pool)  # dlint: disable=guarded-attrs — only called from _adopt_native, under self.lock
            self.c_evictions.inc(freed)
            if self._evict_counter is not None:
                self._evict_counter.inc(freed)
            if freed:
                self.recorder.record("kv_evict", n_pages=freed, lane=lane)
        if fork_src is not None:
            return [self.pool.fork(fork_src)]
        return self.pool.alloc(n)

    def anchor_for(self, lane: int) -> tuple[int, int] | None:
        """(radix node_id, matched token count) of ``lane``'s last
        :meth:`match`, or None when nothing matched — the grouping key
        the scheduler hands the shared n-gram drafter."""
        with self.lock:
            return self._lane_anchor.get(lane)

    def release_lane(self, lane: int) -> None:
        with self.lock:
            pages = self._lane_pages.pop(lane, None)
            self._lane_match_tokens.pop(lane, None)
            self._lane_anchor.pop(lane, None)
            if pages:
                self.pool.release(pages)
            if self.native:
                self.engine.clear_lane_pages(lane)
            self._update_gauges_locked()

    # -- finish ------------------------------------------------------------
    def publish(self, lane: int, tokens: list[int]) -> int:
        """Store ``lane``'s fed ``tokens`` (KV rows [0, len(tokens)) are
        live in its slab) as whole pages. Dedups against the tree first:
        slots the tree already holds are NOT copied again — that is what
        makes a fanned-out system prompt physically one set of pages.
        Returns the number of pages newly stored (0 = full dedup or no
        whole page to store)."""
        if self.native:
            return self._publish_native(lane, tokens)
        ps = self.page_size
        # a lane whose window layers' ring has wrapped has lost its first
        # rows there: nothing of it can be stored (engine.kv_publishable)
        tokens = tokens[: self.engine.kv_publishable(len(tokens))]
        n_full = len(tokens) // ps
        if n_full == 0:
            return 0
        full = list(tokens[: n_full * ps])
        with self.lock:
            mr = self.tree.match(full)
            k_shared = min(mr.n_tokens // ps, n_full)
            n_new = n_full - k_shared
            if n_new == 0:
                return 0
            # Pin the matched prefix across the eviction: under pool
            # pressure the matched leaf itself can be the refcount-1 LRU
            # victim, which would leave ``mr``/``k_shared`` pointing at
            # freed (possibly reallocated) pages and the insert below
            # rebuilding a token path with no pages behind its lower
            # slots. Pinned pages are refcount >= 2 and unevictable.
            self.pool.retain(mr.pages)
            try:
                short = n_new - self.pool.free_pages
                if short > 0:
                    freed = self.tree.evict(short, self.pool)
                    self.c_evictions.inc(freed)
                    if self._evict_counter is not None:
                        self._evict_counter.inc(freed)
                    if freed:
                        self.recorder.record(
                            "kv_evict", n_pages=freed, lane=lane
                        )
                if n_new > self.pool.free_pages:
                    # pool is full of retained/live pages: skip publishing
                    # rather than stall (the stream already served; only
                    # future reuse is lost)
                    self.recorder.record(
                        "kv_publish_skipped", lane=lane, want=n_new,
                        free=self.pool.free_pages,
                    )
                    return 0
                diverged_mid_page = (
                    mr.n_tokens > k_shared * ps and len(mr.pages) > k_shared
                )
                fork_page = mr.pages[k_shared] if diverged_mid_page else None
                pages = self._alloc_publish_pages(fork_page, n_new, lane)
            finally:
                self.pool.release(mr.pages)
            if pages is None:
                return 0
        pool_epoch0 = getattr(self.engine, "kv_pool_epoch", 0)
        try:
            self.engine.kv_publish(lane, pages, start_page=k_shared)
        except BaseException:
            if getattr(self.engine, "kv_pool_epoch", 0) != pool_epoch0:
                # the publish program donated the pool buffer and the
                # engine guard rebuilt it: EVERY page's device contents
                # are gone, so drop all host accounting with them
                logger.exception("kv_publish poisoned the pool; resetting")
                self.reset(reset_device=False)
            else:
                # transient failure before the buffer was touched (e.g.
                # an injected dispatch fault): only this publish's fresh
                # pages are suspect — release them and keep every
                # survivor's pages and the stored prefixes intact
                logger.exception(
                    "kv_publish failed; dropping this publish's pages"
                )
                with self.lock:
                    self.pool.release(pages)
                    self._update_gauges_locked()
            return 0
        with self.lock:
            try:
                self.tree.insert(full, pages, first_slot=k_shared)
            except Exception:
                # insert validates that dedup'd slots still exist on the
                # stored path; a rejection means the accounting raced —
                # drop the new pages and skip the store instead of
                # crashing the scheduler (only future reuse is lost)
                logger.exception("kv radix insert rejected; publish dropped")
                self.pool.release(pages)
                self._update_gauges_locked()
                return 0
            self._update_gauges_locked()
        return n_new

    def _publish_native(self, lane: int, tokens: list[int]) -> int:
        """Native publish = ownership transfer, zero device work: the
        lane already WROTE its KV into its private pool pages, so storing
        a prefix means retaining those pages for the tree and inserting
        the token path. Dedup still applies: slots the tree already holds
        keep the tree's pages (the lane's duplicates are freed at
        release_lane)."""
        ps = self.page_size
        n_full = len(tokens) // ps
        if n_full == 0:
            return 0
        full = list(tokens[: n_full * ps])
        fault = get_fault_plane().draw("dispatch", op="kv_publish")
        if fault is not None:
            # degraded-not-dead, same policy as the slab skip paths: the
            # stream already served, only future reuse is lost
            self.recorder.record(
                "kv_publish_skipped", lane=lane, want=n_full, error=str(fault)
            )
            return 0
        with self.lock:
            lane_list = self._lane_pages.get(lane) or []
            if len(lane_list) < n_full:
                return 0
            mr = self.tree.match(full)
            k_shared = min(mr.n_tokens // ps, n_full)
            n_new = n_full - k_shared
            if n_new == 0:
                return 0
            pages = lane_list[k_shared:n_full]
            # the tree must own its own reference BEFORE insert: the
            # lane's retain dies with release_lane, and a tree pointing
            # at freed pages would hand later admissions recycled KV
            self.pool.retain(pages)
            try:
                self.tree.insert(full, pages, first_slot=k_shared)
            except Exception:
                logger.exception("kv radix insert rejected; publish dropped")
                self.pool.release(pages)
                self._update_gauges_locked()
                return 0
            self._update_gauges_locked()
        return n_new

    def _alloc_publish_pages(
        self, fork_page: int | None, n_new: int, lane: int
    ) -> list[int] | None:
        """Allocate ``n_new`` pages for a publish, copy-on-write-forking
        ``fork_page`` as the first when the stored prefix diverged
        mid-page. Returns None on allocation failure (or an injected
        ``kv_alloc`` fault) — survivable by design: the stream already
        served, only future reuse is lost (same degraded-not-dead policy
        as the full-pool publish skip)."""
        try:
            fault = get_fault_plane().draw("kv_alloc", op="publish")
            if fault is not None:
                raise fault
            if fork_page is None:
                return self.pool.alloc(n_new)
            rest = self.pool.alloc(n_new - 1)
            try:
                return [self.pool.fork(fork_page)] + rest
            except MemoryError:
                self.pool.release(rest)
                raise
        except (MemoryError, InjectedFault) as e:
            self.recorder.record(
                "kv_alloc_failed", lane=lane, want=n_new, error=str(e)
            )
            return None

    def note_hit(self, n_tokens: int) -> None:
        self.c_hits.inc()
        self.c_shared_tokens.inc(n_tokens)

    # -- error path / introspection ----------------------------------------
    def reset(self, reset_device: bool = True) -> None:
        """Drop every page and stored prefix (host and, by default, the
        device buffer) — the big hammer for engine-error recovery paths
        that cannot trust pool contents."""
        with self.lock:
            self.tree.clear()
            self.pool.reset()
            self._lane_pages.clear()
            self._lane_match_tokens.clear()
            self._lane_anchor.clear()
            if self.native:
                self.engine.clear_all_lane_pages()
            self._update_gauges_locked()
        if reset_device:
            self.engine.reset_kv_pool()
        self.recorder.record("kv_pool_reset")

    def release_all_lanes(self) -> None:
        """Scheduler-error path: every lane was dropped, release their
        retains. Pool pages themselves are NOT donated by decode/prefill
        dispatches, so the tree's stored prefixes stay valid."""
        with self.lock:
            for pages in self._lane_pages.values():
                self.pool.release(pages)
            self._lane_pages.clear()
            self._lane_match_tokens.clear()
            self._lane_anchor.clear()
            if self.native:
                self.engine.clear_all_lane_pages()
            self._update_gauges_locked()

    def check(self) -> None:
        with self.lock:
            self.pool.check()

    def debug(self) -> dict:
        """The /v1/debug/kv payload."""
        with self.lock:
            st = self.pool.stats()
            return {
                "page_size": self.page_size,
                "pool": {
                    "total": st.total,
                    "free": st.free,
                    "used": st.used,
                    "shared": st.shared,
                    "cow_forks": st.cow_forks,
                },
                "radix": {
                    "nodes": self.tree.node_count(),
                    "tokens": self.tree.token_count(),
                    "pages": self.tree.n_pages,
                },
                "lanes": {
                    str(lane): len(pages)
                    for lane, pages in self._lane_pages.items()
                },
            }
