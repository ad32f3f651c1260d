"""PreparedQuery: the prepared-statement handle over the plan cache.

``TpuSession.prepare(df)`` / ``SqlSession.prepare(sql)`` return one of
these.  ``execute()`` resolves the template against the session's
:class:`~spark_rapids_tpu.serving.plan_cache.PlanCache` — a hit
re-drains the cached lowered exec tree with ZERO parse/plan/tag/lower
work (the acceptance contract: no ``query.plan``/``query.tag``/
``query.lower`` spans and no jit-cache misses on a hit) — and runs it
through the exact collect machinery plain DataFrames use (admission,
tracing, history, event log, CPU-degrade ladder), so a prepared query
is indistinguishable from an ad-hoc one everywhere downstream.

``execute_stream()`` is the serving-shaped fetch: Arrow record batches
yielded incrementally off the pipelined collect path — the device keeps
at most ``pipeline.depth`` result batches in flight and the producer
blocks when the consumer lags (backpressure comes from the prefetch
stage's bounded queue, parallel/pipeline.py), instead of one giant
table materialization per request.

Concurrency: re-drains of ONE cached exec tree serialize on the entry
lock (the tree is stateful while draining); different templates — and
the same template across different sessions' caches — run concurrently
under the admission scheduler.
"""

from __future__ import annotations

from typing import Iterator, Optional

from spark_rapids_tpu.serving.plan_cache import (
    CacheEntry,
    binding_key,
    sql_template_key,
    template_key,
)


class PreparedQuery:
    """A prepared template: either a native DataFrame plan or a SQL
    text with named parameters (``:name``) bound at execute time."""

    def __init__(self, session, df=None, sql_text: Optional[str] = None,
                 sql_session=None,
                 param_names: Optional[frozenset] = None):
        assert (df is None) != (sql_text is None)
        self._session = session
        self._df = df
        self._sql_text = sql_text
        self._sql_session = sql_session
        self.param_names = param_names or frozenset()
        #: (conf_fingerprint, binding_repr) -> key memo: the structural
        #: key digests in-memory tables; recomputing it per execute
        #: would re-hash the data every time
        self._key_memo: dict = {}
        #: conf_fingerprint -> binding-INDEPENDENT template key (the
        #: admission-batching group: same template, different
        #: bindings, one group)
        self._group_memo: dict = {}
        self.last_plan_hash: Optional[str] = None
        #: in-flight CancelTokens of THIS template's executions (the
        #: PreparedQuery.cancel() scope; serving/cancel.py)
        from spark_rapids_tpu.serving.cancel import TokenSet

        self._inflight = TokenSet()

    # -- resolution -------------------------------------------------- #

    def _key(self, conf, params: Optional[dict]) -> str:
        from spark_rapids_tpu.eventlog import conf_fingerprint
        from spark_rapids_tpu.parallel.mesh import mesh_cache_suffix

        # the mesh suffix is part of the memo key, not just the hashed
        # payload: a new mesh changes the template key under an
        # UNCHANGED conf fingerprint, and a memo keyed on fp alone
        # would keep serving the old mesh's entry
        fp = conf_fingerprint(conf) + mesh_cache_suffix()
        binding = binding_key(params)
        memo = self._key_memo.get((fp, binding))
        if memo is not None:
            return memo
        if self._sql_text is not None:
            key = sql_template_key(self._sql_text, conf, params)
        else:
            key = template_key(self._df._plan, conf)
        # bound memo: conf epochs and bindings are few per template
        if len(self._key_memo) > 64:
            self._key_memo.clear()
        self._key_memo[(fp, binding)] = key
        return key

    def _group_key(self, conf) -> str:
        """The binding-independent template identity this query admits
        under: admission-aware batching (serving/scheduler.py) grants
        queued queries sharing it together, so their scans overlap and
        dedup in flight (docs/work_sharing.md).  SQL templates key on
        normalized text x conf (bindings excluded — 'same template,
        different bindings' is exactly the compatible-plan class);
        DataFrame templates on their structural plan key x conf.
        The group folds the mesh identity too: tenants batch together
        only when they would share the same mesh's program set."""
        from spark_rapids_tpu.eventlog import conf_fingerprint
        from spark_rapids_tpu.parallel.mesh import mesh_cache_suffix

        fp = conf_fingerprint(conf) + mesh_cache_suffix()
        memo = self._group_memo.get(fp)
        if memo is not None:
            return memo
        if self._sql_text is not None:
            key = sql_template_key(self._sql_text, conf, None)
        else:
            key = template_key(self._df._plan, conf)
        if len(self._group_memo) > 64:
            self._group_memo.clear()
        self._group_memo[fp] = key
        return key

    def _resolve(self, params: Optional[dict]) -> tuple:
        """(entry, hit): look the template up in the session plan
        cache; on a miss, parse (SQL) + lower ONCE and insert.  The
        hit/miss verdict is returned, NOT written to the serving
        context here — execute() hands it to _collect_tpu, which
        deposits it inside the query's admission scope (a nested
        query's facts must land in its own record, never the outer
        query's)."""
        from spark_rapids_tpu.eventlog import plan_fingerprint
        from spark_rapids_tpu.plan.planner import plan_query

        conf = self._session.conf
        if params and self._sql_text is None:
            raise ValueError(
                "params are only valid for SQL templates "
                "(prepare(sql) with :name placeholders)")
        key = self._key(conf, params)
        cache = self._session.plan_cache
        entry = cache.lookup(key)
        hit = entry is not None
        if entry is None:
            if self._sql_text is not None:
                df = self._sql_session.sql(self._sql_text,
                                           params=params or {})
            else:
                df = self._df
            exec_, meta = plan_query(df._plan, conf)
            mine = CacheEntry(exec_, meta,
                              plan_fingerprint(meta.explain()), df)
            entry = cache.insert(key, mine)
            if entry is not mine:
                # another thread of this session lowered the same
                # template first; drop the duplicate tree
                exec_.close()
        self.last_plan_hash = entry.plan_hash
        return entry, hit

    # -- execution --------------------------------------------------- #

    def _facts(self, hit: bool,
               extra_facts: Optional[dict]) -> dict:
        """The per-query serving facts deposited inside the admission
        scope.  ``extra_facts`` lets an ingress layer attach its own
        record section (the connect server's peer/wire_bytes/
        translate_ms — docs/connect.md) without a second deposit
        path."""
        facts = {"plan_cache": "hit" if hit else "miss",
                 "admission_group":
                     self._group_key(self._session.conf)}
        if extra_facts:
            facts.update(extra_facts)
        return facts

    def execute(self, params: Optional[dict] = None,
                extra_facts: Optional[dict] = None):
        """Run the template (binding ``params`` for SQL templates) and
        return the full Arrow result table.  Cache hits skip straight
        to draining the cached lowered plan.  The entry's re-drain
        lock is taken INSIDE admission (by _collect_tpu) — taking it
        here would deadlock against an admitted query that
        nested-executes this same template."""
        entry, hit = self._resolve(params)
        out, _qid = entry.df._collect_tpu(
            exec_=entry.exec_, meta=entry.meta,
            drain_lock=entry.lock,
            serving_facts=self._facts(hit, extra_facts),
            token_sink=self._inflight)
        return out

    def cancel(self, reason: str = "cancelled") -> int:
        """Cooperatively cancel every in-flight execution of THIS
        template (narrower than ``session.cancel()``): each raises
        QueryCancelled at its next checkpoint and unwinds cleanly —
        admission slot released, the entry's re-drain lock freed, the
        cached exec tree closed back to its re-drainable state.
        Returns the number of executions newly cancelled.  Requires
        spark.rapids.tpu.serving.cancellation.enabled (the default)."""
        return self._inflight.cancel(reason=reason)

    def execute_stream(self, params: Optional[dict] = None,
                       batch_rows: Optional[int] = None,
                       extra_facts: Optional[dict] = None) -> Iterator:
        """Run the template and yield the result INCREMENTALLY as
        Arrow record batches (optionally re-chunked to ``batch_rows``).
        Backpressure: the device-side producer runs at most the
        pipeline fetch depth ahead of the consumer; a slow consumer
        stalls the producer, not the process.  The admission slot and
        the template's entry lock are held until the stream is drained
        or closed — an abandoned stream must be ``close()``d (or left
        to GC) to release them."""
        entry, hit = self._resolve(params)
        yield from entry.df._stream_tpu(
            exec_=entry.exec_, meta=entry.meta,
            batch_rows=batch_rows, drain_lock=entry.lock,
            serving_facts=self._facts(hit, extra_facts),
            token_sink=self._inflight)

    # -- introspection ----------------------------------------------- #

    def explain(self, params: Optional[dict] = None) -> str:
        """The (cached) lowered plan's annotated report — what
        ``DataFrame.explain()`` would show for this template."""
        from spark_rapids_tpu.eventlog import render_plan_report

        entry, _hit = self._resolve(params)
        return render_plan_report(entry.exec_, entry.meta)

    def __repr__(self) -> str:
        what = ("sql" if self._sql_text is not None
                else type(self._df._plan).__name__)
        return (f"PreparedQuery[{what}, "
                f"params={sorted(self.param_names) or '-'}]")
