"""What the two files that ask the TPU compiler without a chip share (not
collected): the described ``v5e:2x2``, the chip's settings, and the compile
itself. ``tests/test_aot_compile_tpu.py`` compiles the kernels at the
cells' rungs, ``tests/test_aot_train_steps_tpu.py`` whole serve and train
steps (split in ISSUE 45: one file was a worker's 396 s)."""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _as_on_the_chip():
    """Compile cache off: such a compile is written to the persistent
    cache but cannot be read back without a chip, so the next run would
    warn and compile again (guide on-chip-measurement §2.3). 64-bit mode
    off: the suite's conftest turns it on for its fp64 oracles, the chip
    runs without it, and Mosaic refuses the int64 index maps it makes."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# GQA group 1 at the kernels: (query = key-value heads, head_dim, the rung
# the tuner gives the 16k packed mask since ISSUE 35)
_GROUP_ONE = {
    "latent": (20, 256, (256, 512, 5)),  # GLM-4.7-Flash after up-projection
    "looped": (16, 128, (256, 512, 8)),  # Ouro-2.6B
}


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def update_fusions(hlo_text: str) -> tuple[list[str], int]:
    """(the fused computations of a compiled step that hold both a
    ``convolution`` and an instruction whose ``op_name`` lies under
    ``magi_optimizer``: a weight's update inside the matmul of its
    gradient; how many instructions lie under that scope at all)."""
    fused = [
        m.group(1)
        for m in re.finditer(
            r"^%?(fused_computation[\w.\-]*) .*?\{\n(.*?)^\}",
            hlo_text, re.MULTILINE | re.DOTALL,
        )
        if " convolution(" in m.group(2) and "magi_optimizer" in m.group(2)
    ]
    return fused, len(re.findall(r'op_name="[^"]*magi_optimizer', hlo_text))
