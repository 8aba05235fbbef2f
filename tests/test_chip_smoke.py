"""``chip_smoke.py``'s phases at a reduced size on the CPU, with the decode
kernels in interpret mode: the rehearsal of the chip run.  On a TPU the
script runs the same functions at olmo-1b's published width."""
import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.cluster import SliceSpec
from repro.configs import ShapeConfig, registry
from repro.models import api
from repro.parallel.context import LOCAL
from repro.serve.kvpool import KVPool

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(smoke)


@pytest.fixture(scope="module")
def olmo():
    cfg = registry.get_reduced("olmo-1b")
    return cfg, api.init_params(cfg, jax.random.PRNGKey(0))


def test_device_phase_refuses_cpu():
    with pytest.raises(smoke.SmokeFailure, match="needs a TPU.*cpu"):
        smoke.device_phase()


def test_serve_phase_reduced(olmo):
    cfg, params = olmo
    ctx = dataclasses.replace(LOCAL, decode_attn="paged", decode_kv_block=16)
    res = smoke.serve_phase(
        cfg, params, ctx=ctx, requests=2, new_tokens=6,
        spec=SliceSpec(slots=2, max_len=64, prompt_len=16, chunk=4),
        pooled=SliceSpec(slots=2, max_len=64, prompt_len=48, chunk=4,
                         kv_block=16, kv_share=True, kv_blocks=16))
    # interpret mode lowers the kernel to plain HLO: no custom call here
    assert res["decode_has_kernel"] is False
    assert res["identical_streams"] == 2
    assert res["pooled"]["shared_prompt_tokens"] == 2 * 32
    assert res["pooled"]["leaked_blocks"] == 0
    assert set(res["kernel_max_abs_err"]) == {"plain", "int8", "block_table"}


def test_pooled_spec_covers_its_slots():
    """The chip run's pool is large enough for the engine to build it."""
    p = smoke.POOLED_SPEC
    KVPool(num_blocks=p.kv_blocks, block_size=p.kv_block, slots=p.slots,
           blocks_per_slot=p.max_len // p.kv_block)


def test_train_phase_reduced(olmo):
    cfg, _ = olmo
    res = smoke.train_phase(cfg, layers=2, steps=2,
                            shape=ShapeConfig("t", "train", 32, 2))
    assert len(res["losses"]) == 2 and res["layers"] == 2


@pytest.mark.parametrize("env_dir", [False, True], ids=["checkout", "env"])
def test_compile_cache_location(tmp_path, env_dir):
    """The cache lands in JAX_COMPILATION_CACHE_DIR when it is set, else in
    the checkout's .jax_cache."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(7.0))"
            ".block_until_ready()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    want = (tmp_path / "cache") if env_dir else ROOT / ".jax_cache"
    assert out.stdout.split() == [str(want)] * 2
    assert any(want.iterdir())
