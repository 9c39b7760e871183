"""Jitted programs built once per static configuration and reused.

A function wrapped in ``jax.jit`` afresh on every call never hits jit's
in-memory cache: each call traces and lowers it again, even where a
persistent compilation cache spares the backend compile.  ``program``
keeps one jitted function per builder and static values.  Every program
of every lane of ``repro.core.engine`` is built here, so a repeat
``run_adaptive`` or ``run_fixed`` call with the same graph shape, mesh
and configuration reuses its programs and traces and lowers nothing.

The contract of a builder: ``build(*static)`` returns the function to
jit, every value it closes over is one of ``static`` (hashable, compared
by value: ints, floats, strings, ``RunContext``, estimators, functions,
meshes), and every array is an argument of the returned function.  An
array held in a closure would be a constant of the cached program, and
a second graph of the same shape would get the first graph's results.
A ``shard_map`` inside a program takes its specs from the arguments'
structure, inside the traced function.

``ProgramTally`` counts, for one run, which programs it called and
whether each was built in the run or came from the cache.
"""
from __future__ import annotations

import contextvars
import functools

import jax

__all__ = ["Program", "ProgramTally", "program"]

# the run whose programs are being counted (None outside any)
_TALLY = contextvars.ContextVar("program_tally", default=None)


class Program:
    """One jitted function; each call is noted in the open tally as
    built (the call added an executable to jit's cache: it traced and
    lowered) or reused."""

    def __init__(self, fn):
        self._jit = jax.jit(fn)

    def __call__(self, *args):
        # _cache_size: the executables jit holds, one per argument
        # signature (shapes, dtypes, placement)
        before = self._jit._cache_size()
        out = self._jit(*args)
        tally = _TALLY.get()
        if tally is not None:
            tally.note(self, self._jit._cache_size() > before)
        return out


@functools.lru_cache(maxsize=64, typed=True)
def program(build, *static) -> Program:
    """The jitted ``build(*static)``, built on the first request for
    these values and reused after; ``program.cache_clear()`` drops them
    all."""
    return Program(build(*static))


class ProgramTally:
    """The programs one run called, as a context manager: ``built``
    counts those that traced and lowered in the run, ``reused`` those
    every call of which jit's cache served."""

    def __init__(self):
        self._built = {}
        self._token = None

    def note(self, prog: Program, built: bool):
        self._built[prog] = self._built.get(prog, False) or built

    @property
    def built(self) -> int:
        return sum(self._built.values())

    @property
    def reused(self) -> int:
        return len(self._built) - self.built

    def __enter__(self):
        self._token = _TALLY.set(self)
        return self

    def __exit__(self, *exc):
        _TALLY.reset(self._token)
        return False
