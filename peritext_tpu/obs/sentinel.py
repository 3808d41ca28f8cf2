"""Runtime recompile sentinel (the runtime half of graftlint PTL004)."""

from __future__ import annotations

import logging
import re
from typing import Dict, Optional

from .metrics import Counters, GLOBAL_COUNTERS

#: jax's log_compiles emission: "Compiling jit(<site>) with global shapes
#: and types ..." — the site is the name inside ``jit(...)``.  Matched with ``search``, anywhere in the record — handlers downstream of
#: other logging layers can receive the message PREFIXED (formatter noise,
#: "%(asctime)s ... Compiling f ...") or MULTI-LINE (a "Finished tracing +
#: transforming <site> ..." line batched ahead of the Compiling line), and
#: an anchored match silently counted zero compiles for those.  The word
#: boundary keeps "XLA compilation"/"Recompiling"-style prose from
#: false-matching.
_COMPILE_MSG_RE = re.compile(r"\bCompiling jit\(([^)\s]+)\)")


class RecompileSentinel(logging.Handler):
    """Runtime guard for the compile-shape discipline (DESIGN.md "compile-
    shape discipline", graftlint PTL004): counts XLA compilations **per jit
    site** so steady-state streaming rounds can assert *zero* recompiles.

    Backed by ``jax_log_compiles``: while active, jax logs one
    ``Compiling <site> ...`` record per executable built, and this handler
    (attached to the ``"jax"`` logger) tallies it — no private APIs, no
    tracing overhead beyond the log call.  Counts land three ways:

    * :attr:`counts` — ``{site: compiles}`` on the sentinel itself;
    * ``jit.compiles.<site>`` / ``jit.compiles_total`` on the target
      :class:`Counters` (default :data:`GLOBAL_COUNTERS`), which
      :func:`~.metrics.health_snapshot` exports;
    * ``health_snapshot(sentinel=s)`` embeds the per-site dict directly.

    Use as a context manager; :meth:`mark` + :meth:`assert_steady_state`
    express the invariant tests care about::

        with RecompileSentinel() as s:
            warmup_rounds(session)
            s.mark()
            steady_rounds(session)
            s.assert_steady_state("steady-state streaming rounds")
    """

    def __init__(self, counters: Optional[Counters] = None, logger: str = "jax"):
        super().__init__(level=logging.DEBUG)
        self.counts: Dict[str, int] = {}
        self._marked: Dict[str, int] = {}
        self._counters = counters if counters is not None else GLOBAL_COUNTERS
        self._logger = logging.getLogger(logger)
        self._prev_log_compiles: Optional[bool] = None
        self._active = False

    # -- logging.Handler ------------------------------------------------------

    def emit(self, record: logging.LogRecord) -> None:
        try:
            message = record.getMessage()
        except Exception:  # graftlint: boundary(malformed foreign log records are ignored, never raised into the workload)
            return
        m = _COMPILE_MSG_RE.search(message)
        if m is None:
            return
        site = m.group(1)
        self.counts[site] = self.counts.get(site, 0) + 1
        self._counters.add(f"jit.compiles.{site}")
        self._counters.add("jit.compiles_total")

    # -- lifecycle ------------------------------------------------------------

    def start(self) -> "RecompileSentinel":
        if self._active:
            return self
        import jax

        self._prev_log_compiles = bool(jax.config.jax_log_compiles)
        jax.config.update("jax_log_compiles", True)
        self._logger.addHandler(self)
        self._active = True
        return self

    def stop(self) -> None:
        if not self._active:
            return
        self._logger.removeHandler(self)
        try:
            import jax

            jax.config.update("jax_log_compiles", self._prev_log_compiles)
        except Exception:  # graftlint: boundary(best-effort config restore on teardown; the counts already collected stay valid)
            pass
        self._active = False

    def __enter__(self) -> "RecompileSentinel":
        return self.start()

    def __exit__(self, *exc_info) -> None:
        self.stop()

    # -- assertions -----------------------------------------------------------

    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def mark(self) -> None:
        """Snapshot the current counts; :meth:`since_mark` and
        :meth:`assert_steady_state` measure growth from here."""
        self._marked = dict(self.counts)

    def since_mark(self) -> Dict[str, int]:
        """Per-site compiles since :meth:`mark` (empty dict = steady state)."""
        return {
            site: n - self._marked.get(site, 0)
            for site, n in sorted(self.counts.items())
            if n > self._marked.get(site, 0)
        }

    def assert_steady_state(self, what: str = "steady-state rounds") -> None:
        fresh = self.since_mark()
        if fresh:
            raise AssertionError(
                f"{what} triggered {sum(fresh.values())} recompile(s): {fresh} "
                "— a per-round shape escaped the padded-shape tables "
                "(see DESIGN.md compile-shape discipline / graftlint PTL004)"
            )
