"""Every Pallas kernel entry point compiles for a TPU v5e chip.

The chip is described, not attached: the TPU compiler that ships with
``jax`` compiles against ``v5e:2x2``'s topology description, so these tests
catch what interpret mode cannot (Mosaic's tiling and dtype rules) on any
host.  Shapes are one full-width OLMo-1B block on the flatten-once
``(rows, 1024)`` layout.  The topology is described inside a fixture, never
at import time: one process at a time may load the TPU library, and every
test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import LANE
from repro.kernels import gossip_mix as gm
from repro.kernels import momentum as mom
from repro.kernels import qsgd_quant as qq
from repro.kernels import row_gather as rg
from repro.kernels import sign_compress as sc
from repro.kernels import topk_select as tk

ROWS = 49152          # one OLMo-1B block: 4·2048² + 2·2048·8192 params
GATHER = ROWS // 100  # touched rows shipped by the sparse wire
TOPK_W = 11           # ceil(0.01 · 1024)
F32, I32, U8 = jnp.float32, jnp.int32, jnp.uint8


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


KERNELS = {
    "momentum": (lambda x, m, g, lr: mom.momentum_update(
        x, m, g, lr, mu=0.9, wd=1e-4, interpret=False),
        [((ROWS, LANE), F32)] * 3 + [((), F32)]),
    "gossip_mix": (lambda a, b, c: gm.gossip_mix(
        (a, b, c), weights=(0.5, 0.25, 0.25), interpret=False),
        [((ROWS, LANE), F32)] * 3),
    "sign_pack": (lambda x, c: sc.sign_pack_pallas(x, c, interpret=False),
                  [((ROWS, LANE), F32), ((ROWS, 1), F32)]),
    "sign_unpack": (lambda p, s: sc.sign_unpack_pallas(p, s,
                                                       interpret=False),
                    [((ROWS, LANE // 8), U8), ((ROWS, 1), F32)]),
    "topk_select": (lambda x, c: tk.topk_select_pallas(
        x, c, fraction=0.01, interpret=False),
        [((ROWS, LANE), F32), ((ROWS, 1), F32)]),
    "topk_scatter": (lambda i, v: tk.topk_scatter_pallas(i, v,
                                                         interpret=False),
                     [((ROWS, TOPK_W), I32), ((ROWS, TOPK_W), F32)]),
    "row_gather": (lambda x, i, c: rg.row_gather_pallas(x, i, c,
                                                        interpret=False),
                   [((ROWS, LANE), F32), ((GATHER,), I32), ((ROWS,), F32)]),
    "row_scatter": (lambda i, v: rg.row_scatter_pallas(i, v, rows=ROWS,
                                                       interpret=False),
                    [((GATHER,), I32), ((GATHER, LANE), F32)]),
}
# QSGD packs 2, 4 or 8 bits per level: one entry per packing
for _levels, _bits in ((1, 2), (7, 4), (16, 8)):
    KERNELS[f"qsgd_quant_{_bits}bit"] = (
        lambda x, _l=_levels: qq.qsgd_quant_pallas(x, levels=_l,
                                                   interpret=False),
        [((ROWS, LANE), F32)])
    KERNELS[f"qsgd_dequant_{_bits}bit"] = (
        lambda p, n, _l=_levels: qq.qsgd_dequant_pallas(p, n, levels=_l,
                                                        interpret=False),
        [((ROWS, LANE * _bits // 8), U8), ((ROWS, 1), F32)])


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = KERNELS[name]
    specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in args]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


# -- the fused round's loop carry ---------------------------------------------
# One worker on one chip: the W = [[1]] mix folds away, so nothing after the
# round's p-step loop pins the layout of the params and momentum it carries.
# Widths are multiples of the chip's 128 lanes, so every leaf's default
# layout on the chip is major-to-minor, the one the loop is pinned to.
_ENTRY = re.compile(r"^ENTRY .*?^}", re.S | re.M)
_PARAM = re.compile(r"^\s+%\S+ = (\w+\[[\d,]*\])(\{[^}]*\}) parameter\(",
                    re.M)
_WHILE = re.compile(r"^\s+%\S+ = \((.*?)\) while\(", re.M)
_COPY = re.compile(r"^\s+%\S+ = (\w+\[[\d,]*\])\{[^}]*\} copy\(", re.M)
_ARRAY = re.compile(r"(\w+\[[\d,]*\])(\{[^}]*\})")


def _dims(layout: str) -> str:
    """``{2,3,1,0}`` from ``{2,3,1,0:T(8,128)(2,1)S(1)}``: the minor-to-major
    order alone (tiling and memory space are the chip's own)."""
    return layout.split(":")[0].rstrip("}") + "}"


@pytest.mark.parametrize("optimizer,overlap", [
    ("pd_sgdm", False), ("cpd_sgdm", False), ("pd_sgdm", True)])
def test_round_loop_carries_argument_layout(optimizer, overlap, one_chip):
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import ModelCfg, OptimCfg, ParallelCfg, RunCfg
    from repro.configs.shapes import InputShape
    from repro.launch.runtime import build_train

    model = ModelCfg(name="tiny", arch_type="dense", n_layers=2, d_model=128,
                     n_heads=4, n_kv_heads=4, d_ff=256, vocab=256,
                     norm="nonparametric", tie_embeddings=True,
                     param_dtype="bfloat16", compute_dtype="bfloat16")
    run = RunCfg(model=model, parallel=ParallelCfg(remat="full"),
                 optim=OptimCfg(name=optimizer, p=4, overlap=overlap))
    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    pack = build_train(run, mesh, InputShape("t", 32, 2, "train"))

    def struct(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh), tree, shardings)
    hlo = pack.train_round.lower(
        struct(pack.params_struct, pack.params_sharding),
        struct(pack.state_struct, pack.state_sharding),
        struct(pack.round_batch_struct, pack.round_batch_sharding),
    ).compile().as_text()
    entry = _ENTRY.search(hlo).group(0)

    leaves = {}   # whole param / momentum leaf -> its argument layouts
    for shape, layout in _PARAM.findall(entry):
        leaves.setdefault(shape, set()).add(_dims(layout))
    hlo_dtype = {"bfloat16": "bf16", "float32": "f32"}
    whole = set()  # each param and its f32 momentum, as the HLO writes them
    for s in jax.tree_util.tree_leaves(pack.params_struct):
        if s.ndim >= 2:
            dims = ",".join(map(str, s.shape))
            whole |= {f"{hlo_dtype[jnp.dtype(s.dtype).name]}[{dims}]",
                      f"f32[{dims}]"}
    assert whole <= set(leaves), (whole, leaves)

    loops = _WHILE.findall(entry)
    assert loops, "the round's p-step loop was not found"
    carried = [(shape, _dims(layout)) for loop in loops
               for shape, layout in _ARRAY.findall(loop) if shape in whole]
    assert carried, "the loop carries no param or momentum leaf"
    moved = sorted({(s, l) for s, l in carried if l not in leaves[s]})
    assert not moved, f"the loop carries leaves in another layout: {moved}"
    copies = sorted({s for s in _COPY.findall(entry) if s in whole})
    assert not copies, f"ENTRY relayouts whole leaves: {copies}"
