"""The control at a size a test run holds: the plain reference computed in
float8 e4m3, one precision below the configurations' bfloat16, put in the
program's place, must fail the limit that sound runs of the program pass."""
from __future__ import annotations

import jax
import pytest

from bench import control
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    yield tiny.make_root(tmp_path_factory.mktemp("control"))
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("cell", ["tiny-qwen3-tiny", "tiny-rwkv6-tiny"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_is_not_correct_where_the_program_is(root, cell, seed):
    r = control.readings(root, cell, seed, 1.5, require_tpu=False)
    limit = tiny.CELL["check"]["limit_gap_rms"]
    assert r["program"] <= limit < r["control"], r
