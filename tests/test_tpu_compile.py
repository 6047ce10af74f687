"""Real-width compile rehearsals of the Pallas kernels for a TPU v5e chip.

Each case lowers and compiles one kernel for a *described* (not attached)
v5e chip, so Mosaic's refusals — tile-illegal block shapes, unsupported
primitives, VMEM overruns — surface here instead of on the chip.  Nothing
runs: results and times need the chip (``chip_smoke.py``).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.  The kernels are called directly (not through the auto-dispatch
wrappers, which see the CPU backend here and would pick interpret mode or
the jnp reference).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.matmul import matmul_acc_pallas
from repro.kernels.minplus import minplus_pallas
from repro.kernels.paged_attention import paged_attention_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile is written to the persistent cache but
        # cannot be read back without a chip: keep the cache out of it
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("arch", ["chatglm3-6b", "llama3.2-3b"])
def test_paged_attention_compiles(one_chip, arch):
    """Decode shapes of the arch: 8 slots, 16-token pages, 128-page tables."""
    cfg = configs.get(arch)
    b, blk, pages, n_blocks = 8, 16, 128, 1024
    hkv, rep, hd = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.hd
    arena = ((n_blocks, hkv, blk, hd), jnp.bfloat16)
    text = _compile_text(paged_attention_pallas, one_chip,
                         ((b, hkv, rep, hd), jnp.bfloat16), arena, arena,
                         ((b, pages), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text
    # device-trace readers find the kernel by its name and its first
    # operand, the (slots, pages) block table
    call = re.search(r"%paged_attention\.\d+ = .*?custom-call\((%[\w.\-]+)",
                     text)
    assert call, "no custom call named paged_attention"
    table = re.search(re.escape(call.group(1)) + r" = (s32\[\d+,\d+\])", text)
    assert table and table.group(1) == f"s32[{b},{pages}]"


def test_matmul_acc_compiles(one_chip):
    n = 4096
    text = _compile_text(matmul_acc_pallas, one_chip,
                         ((n, n), jnp.bfloat16), ((n, n), jnp.bfloat16),
                         ((n, n), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    """chatglm3-6b prefill: 32 query heads over 2 KV heads, 2048 tokens."""
    cfg = configs.get("chatglm3-6b")
    s = 2048
    text = _compile_text(flash_attention_pallas, one_chip,
                         ((1, cfg.n_heads, s, cfg.hd), jnp.bfloat16),
                         ((1, cfg.n_kv_heads, s, cfg.hd), jnp.bfloat16),
                         ((1, cfg.n_kv_heads, s, cfg.hd), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_minplus_compiles(one_chip):
    n = 1024
    text = _compile_text(minplus_pallas, one_chip, ((n, n), jnp.float32),
                         ((n, n), jnp.float32))
    assert "tpu_custom_call" in text
