"""Fixtures shared by the test files."""
import jax
import pytest

from repro.core.programs import program


def clear_programs():
    """Forget every built program: jit's in-memory caches and the
    program cache (``repro.core.programs``), so the next ``run_adaptive``
    call traces and lowers all of its programs."""
    jax.clear_caches()
    program.cache_clear()


@pytest.fixture
def cold_programs():
    """A test that starts with no program built."""
    clear_programs()
