"""Timers the harness puts around named callables of the program, from the
benchmark's own files (spans inside the program are a later PR's). Each call
is recorded with its parent among the wrapped calls, so a reader can take a
call's self time; each also writes a jax.profiler.TraceAnnotation, which puts
the same span on the profiler's clock beside the device's operations."""

from __future__ import annotations

import importlib
import threading
import time


def resolve(target: str):
    """'pkg.mod:Class.attr' -> (owner object, attribute name, callable)."""
    mod_name, _, qual = target.partition(":")
    owner = importlib.import_module(mod_name)
    parts = qual.split(".")
    for p in parts[:-1]:
        owner = getattr(owner, p)
    return owner, parts[-1], getattr(owner, parts[-1])


class CallRecorder:
    def __init__(self):
        self.calls: list[dict] = []  # {name, t0, t1, parent}
        self._stack = threading.local()
        self._undo: list[tuple] = []  # (target, owner, attr, original)
        self._lock = threading.Lock()

    def wrap(self, target: str) -> None:
        import jax

        owner, attr, fn = resolve(target)
        if getattr(fn, "_bench_wrapped", False):
            return
        rec = self

        def call(*a, **kw):
            stack = getattr(rec._stack, "s", None)
            if stack is None:
                stack = rec._stack.s = []
            entry = {"name": target, "t0": 0.0, "t1": 0.0,
                     "parent": stack[-1] if stack else None}
            with rec._lock:
                idx = len(rec.calls)
                rec.calls.append(entry)
            stack.append(idx)
            with jax.profiler.TraceAnnotation(target):
                entry["t0"] = time.perf_counter()
                try:
                    return fn(*a, **kw)
                finally:
                    entry["t1"] = time.perf_counter()
                    stack.pop()

        call._bench_wrapped = True
        call.__wrapped__ = fn
        setattr(owner, attr, call)
        self._undo.append((target, owner, attr, fn))

    def names(self) -> list[str]:
        return [u[0] for u in self._undo]

    def unwrap_all(self) -> None:
        for _target, owner, attr, fn in reversed(self._undo):
            setattr(owner, attr, fn)
        self._undo.clear()

    def durations(self, targets, t0: float, t1: float,
                  self_time: bool = False) -> list[float]:
        """Seconds of each finished call of `targets` that started in
        [t0, t1]; with self_time, less the time of its wrapped children."""
        targets = set(targets)
        child = {}
        if self_time:
            for c in self.calls:
                if c["parent"] is not None and c["t1"]:
                    child[c["parent"]] = (child.get(c["parent"], 0.0)
                                          + c["t1"] - c["t0"])
        out = []
        for i, c in enumerate(self.calls):
            if c["name"] in targets and c["t1"] and t0 <= c["t0"] <= t1:
                out.append(c["t1"] - c["t0"] - child.get(i, 0.0))
        return out
