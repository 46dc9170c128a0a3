"""Recompile watchdog: observe every XLA compile, and optionally FAIL on one.

The serving engine's whole shape discipline (bucket lattice, bounded
program set — `serving/engine/buckets.py`) exists so that steady-state
serving never re-jits.  Until now that was a comment; this module makes it
an enforced invariant:

  * every backend compile is recorded as `(program key, compile wall s)`
    where the program key is the jitted function name + its abstract input
    shapes — the exact identity the jit cache misses on;
  * after `arm()`, any further compile is a *violation*: with
    `raise_on_violation=True` (default) the `UnexpectedCompile` is raised
    from inside the compile itself, so the offending `jit` call site is on
    the stack; `check()` re-raises for callers that prefer to poll.

Two independent signals are tapped (they cross-check each other):

  * jax's compile log records (`jax._src.interpreters.pxla` "Compiling
    <name> with global shapes ..." + `jax._src.dispatch` "Finished XLA
    compilation of jit(<name>) in <s> sec"), captured by installing this
    handler at DEBUG level — jax emits them regardless of
    `jax_log_compiles`, at DEBUG priority, so nothing is printed;
  * `jax.monitoring`'s `/jax/core/compile/backend_compile_duration` event,
    a name-free backend-compile count `check()` also compares against (in
    case a jax upgrade reword the log messages).

`install()` bumps the two jax loggers to DEBUG and restores their previous
levels on `uninstall()`; use the instance as a context manager for scoped
watching.  Compile records are mirrored into `obs.trace`/`obs.metrics`
when observability is enabled.
"""
from __future__ import annotations

import dataclasses
import logging
import re
import threading
import time
from typing import Dict, List, Optional

_COMPILING_RE = re.compile(
    r"^Compiling (\S+) with global shapes and types (.*?)\.\s*Argument",
    re.DOTALL)
_FINISHED_RE = re.compile(
    r"^Finished XLA compilation of (?:jit\()?(.*?)\)? in ([0-9.eE+-]+) sec")

_JAX_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class UnexpectedCompile(RuntimeError):
    """An armed CompileWatch saw a compile it was promised would not happen."""


@dataclasses.dataclass(frozen=True)
class CompileRecord:
    key: str          # "<fn name> <abstract input shapes>"
    name: str
    wall_s: float
    armed: bool       # recorded while the watch was armed (= a violation)
    t_s: float        # process-clock time of the record


# jax.monitoring listeners cannot be unregistered individually, so one
# module-level dispatcher forwards backend-compile events to whichever
# watches are currently installed.
_active_watches: "Set[CompileWatch]" = set()
_monitoring_hooked = False
_hook_lock = threading.Lock()


def _on_backend_compile(event: str, duration: float, **kw) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    for w in list(_active_watches):
        w._backend_compile(duration)


def _ensure_monitoring_hook() -> None:
    global _monitoring_hooked
    with _hook_lock:
        if _monitoring_hooked:
            return
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(
            _on_backend_compile)
        _monitoring_hooked = True


class CompileWatch(logging.Handler):
    """Record (and optionally forbid) XLA compiles.  See module docstring."""

    def __init__(self, raise_on_violation: bool = True):
        super().__init__(level=logging.DEBUG)
        self.raise_on_violation = raise_on_violation
        self.records: List[CompileRecord] = []
        self.violations: List[CompileRecord] = []
        self.backend_compiles = 0          # monitoring-event count
        self.armed = False
        self._armed_at_backend = 0
        self._pending: Dict[str, str] = {}  # fn name -> program key
        self._prev_levels: Optional[Dict[str, int]] = None
        self._rec_lock = threading.Lock()

    # -- install / uninstall -------------------------------------------------

    def install(self) -> "CompileWatch":
        _ensure_monitoring_hook()
        self._prev_levels = {}
        self._prev_propagate = {}
        for name in _JAX_COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            self._prev_levels[name] = lg.level
            self._prev_propagate[name] = lg.propagate
            if not lg.isEnabledFor(logging.DEBUG):
                lg.setLevel(logging.DEBUG)
            # the DEBUG records we force through must not reach jax's own
            # stream handler (they'd spam stderr); restored on uninstall
            lg.propagate = False
            lg.addHandler(self)
        _active_watches.add(self)
        return self

    def uninstall(self) -> None:
        _active_watches.discard(self)
        if self._prev_levels is None:
            return
        for name, lvl in self._prev_levels.items():
            lg = logging.getLogger(name)
            lg.removeHandler(self)
            lg.setLevel(lvl)
            lg.propagate = self._prev_propagate[name]
        self._prev_levels = None

    def __enter__(self) -> "CompileWatch":
        return self.install()

    def __exit__(self, *exc) -> bool:
        self.uninstall()
        return False

    # -- arming --------------------------------------------------------------

    def arm(self) -> None:
        """From now on, every compile is a violation.  Call after warmup /
        `Engine.calibrate_step_s()` to enforce the bounded-program claim."""
        self.armed = True
        self._armed_at_backend = self.backend_compiles

    def disarm(self) -> None:
        self.armed = False

    def check(self) -> None:
        """Raise UnexpectedCompile if any compile happened while armed —
        from the parsed log records, or (cross-check) from the name-free
        backend-compile event count."""
        if self.violations:
            keys = ", ".join(v.key for v in self.violations[:4])
            raise UnexpectedCompile(
                f"{len(self.violations)} unexpected compile(s) while armed: "
                f"{keys}")
        if self.armed and self.backend_compiles > self._armed_at_backend:
            raise UnexpectedCompile(
                f"{self.backend_compiles - self._armed_at_backend} backend "
                f"compile event(s) while armed (log records missed them)")

    # -- event sinks ---------------------------------------------------------

    def _backend_compile(self, duration: float) -> None:
        self.backend_compiles += 1

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        m = _COMPILING_RE.match(msg)
        if m:
            name, shapes = m.group(1), " ".join(m.group(2).split())
            with self._rec_lock:
                self._pending[name] = f"{name} {shapes}"
            return
        m = _FINISHED_RE.match(msg)
        if not m:
            return
        name, secs = m.group(1), float(m.group(2))
        with self._rec_lock:
            key = self._pending.pop(name, name)
            rec = CompileRecord(key=key, name=name, wall_s=secs,
                                armed=self.armed, t_s=time.perf_counter())
            self.records.append(rec)
            if self.armed:
                self.violations.append(rec)
        self._mirror(rec)
        if rec.armed and self.raise_on_violation:
            raise UnexpectedCompile(
                f"unexpected compile while armed: {rec.key} "
                f"({rec.wall_s * 1e3:.1f} ms)")

    def _mirror(self, rec: CompileRecord) -> None:
        """Copy the record into the obs trace/metrics when enabled."""
        from . import enabled, get_metrics, get_tracer
        if not enabled():
            return
        get_tracer().instant("compile", cat="compile", key=rec.key,
                             wall_s=rec.wall_s, armed=rec.armed)
        get_metrics().counter("compile.count").inc()
        get_metrics().histogram("compile.wall_s").observe(rec.wall_s)
        if rec.armed:
            get_metrics().counter("compile.violations").inc()

    # -- export --------------------------------------------------------------

    def table(self) -> List[dict]:
        return [dataclasses.asdict(r) for r in self.records]

    def to_json(self) -> dict:
        return {
            "records": self.table(),
            "violations": [dataclasses.asdict(r) for r in self.violations],
            "backend_compiles": self.backend_compiles,
            "armed": self.armed,
        }
