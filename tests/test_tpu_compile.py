"""Compile the recommender's Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler in the installed ``libtpu`` compiles
for a topology that is only described, so what Mosaic or XLA would refuse
on the chip (unaligned block or slice shapes, primitives with no Mosaic
lowering, too much VMEM) fails here, at no chip time. Nothing runs, so
results are the interpret-mode conformance tests' job (test_kernels.py).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file. Keep these tests in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.inbatch_loss import inbatch_loss_rows_pallas
from repro.kernels.ivf import dma_rows, ivf_list_topk_pallas
from repro.kernels.seg_aggr import seg_aggr_pallas
from repro.kernels.table_rows import gather_cols_pallas, scatter_cols_pallas
from repro.kernels.topk import chunked_topk_pallas
from repro.kernels.window_pairs import window_pair_ids_pallas
from repro.sampling.pairs import window_positions

# ub-sized shapes (graph/generator.py: 20,000 items)
ITEMS, QUERIES, K = 20_000, 512, 100
NLIST, NPROBE, LPAD = 64, 8, 1250  # balance_factor 4 bounds lpad at 4I/nlist


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of the filesystem
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *shapes):
    """Compile ``fn`` for the described chip; return the optimized HLO."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _cases(d, spec):
    """name -> (kernel wrapper with interpret off, argument shapes)."""
    dc = -(-d // 128) * 128  # the index stores lane-padded codes
    ip = ITEMS + dma_rows(LPAD)
    return {
        "seg_aggr": (
            functools.partial(seg_aggr_pallas, mode="mean"),
            [spec((4096, 4, d), jnp.float32), spec((4096, 4), jnp.bool_)],
        ),
        "window_pairs": (
            functools.partial(
                window_pair_ids_pallas,
                positions=tuple(map(tuple, window_positions(6, 2))),
            ),
            [spec((512, 6), jnp.int32)],
        ),
        "inbatch_loss": (
            inbatch_loss_rows_pallas,
            [spec((256, d), jnp.float32), spec((256, d), jnp.float32)],
        ),
        "topk": (  # with the exclusion epilogue
            lambda q, it, ex: chunked_topk_pallas(
                q, it, K, exclude=ex, item_chunk=1024
            ),
            [spec((QUERIES, d), jnp.float32), spec((ITEMS, d), jnp.float32),
             spec((QUERIES, 16), jnp.int32)],
        ),
        "table_gather": (  # the rows a sparse step pulls, from a table's transpose
            gather_cols_pallas,
            [spec((d, ITEMS), jnp.float32), spec((4096,), jnp.int32)],
        ),
        "table_scatter": (
            scatter_cols_pallas,
            [spec((d, ITEMS), jnp.float32), spec((4096,), jnp.int32),
             spec((d, 4096), jnp.float32)],
        ),
        "ivf": (
            functools.partial(ivf_list_topk_pallas, lpad=LPAD, shortlist=4 * K),
            [spec((QUERIES, d), jnp.float32), spec((ip, dc), jnp.int8),
             spec((ip, 1), jnp.float32), spec((QUERIES, NPROBE), jnp.int32),
             spec((QUERIES, NPROBE), jnp.int32)],
        ),
    }


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize(
    "name", ["seg_aggr", "window_pairs", "inbatch_loss", "topk", "ivf",
             "table_gather", "table_scatter"]
)
def test_kernel_compiles_for_v5e(one_chip, name, d):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, shapes = _cases(d, spec)[name]
    hlo = _compile_for_chip(fn, *shapes)
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the program"


@pytest.mark.parametrize("d", [64, 128])
def test_ivf_search_program_compiles_for_v5e(one_chip, d, monkeypatch):
    """The whole jitted shortlist program the index serves on TPU: centroid
    probe, the gather-then-score kernel, selection and exclusion."""
    from repro.kernels import ops
    from repro.retrieval.ivf import _ivf_shortlist

    # on this CPU host ops picks interpret mode; the chip would not
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    dc = -(-d // 128) * 128
    ip = ITEMS + dma_rows(LPAD)
    fn = functools.partial(
        _ivf_shortlist, nprobe=NPROBE, shortlist=4 * K, lpad=LPAD,
        backend="pallas",
    )
    hlo = _compile_for_chip(
        fn,
        spec((QUERIES, d), jnp.float32), spec((QUERIES, 16), jnp.int32),
        spec((NLIST, d), jnp.float32), spec((ip, dc), jnp.int8),
        spec((ip, 1), jnp.float32), spec((ITEMS,), jnp.int32),
        spec((NLIST + 1,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.quick
def test_ivf_kernel_is_what_tpu_selects():
    """On a TPU the index serves through the kernel compiled above."""
    from repro.retrieval import IVFConfig, IVFIndex

    rng = np.random.default_rng(0)
    items = rng.normal(size=(300, 8)).astype(np.float32)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        idx = IVFIndex.build(items, IVFConfig(nlist=8))
    assert idx._backend == "pallas"
    assert idx._dev["codes"].shape[1] % 128 == 0  # lane-padded for the DMA


@pytest.mark.parametrize("rows", ["trainer", "xla"])
def test_sparse_update_leaves_the_table_in_place_on_v5e(one_chip, rows,
                                                        monkeypatch):
    """The trainer's sparse update (row-wise AdaGrad; the table and its
    accumulators donated) on an f32 (65,536, 64) table, which the chip keeps
    column-major. With the row ops the trainer picks for that chip the
    optimized program holds no table-sized copy; with XLA's row gather and
    scatter, the control, XLA copies the whole table to row-major and back,
    which shows this test can see such a copy."""
    import re

    from repro.embedding import gather_rows, scatter_rows
    from repro.embedding import optimizer as emb_opt
    from repro.kernels import ops
    from repro.train.trainer import table_row_ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    n, d, bucket = 65_536, 64, 4096

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    table = spec((n, d), jnp.float32)
    gather, scatter = (
        table_row_ops({"emb/node": table},
                      next(iter(one_chip.device_set)))["emb/node"]
        if rows == "trainer" else (gather_rows, scatter_rows)
    )
    if rows == "trainer":
        assert gather is not gather_rows  # the chip keeps the table by columns

    def update(t, acc, ids, g):
        new, state = emb_opt.rowwise_adagrad_scatter_update(
            {"emb/node": t}, {"emb/node": g}, {"emb/node": ids},
            emb_opt.RowAdagradState(accum={"emb/node": acc}), lr=0.2,
            rows={"emb/node": gather(t, ids)}, scatter={"emb/node": scatter},
        )
        return new["emb/node"], state.accum["emb/node"]

    hlo = jax.jit(update, donate_argnums=(0, 1)).lower(
        table, spec((n, 1), jnp.float32), spec((bucket,), jnp.int32),
        spec((bucket, d), jnp.float32),
    ).compile().as_text()
    copies = re.findall(rf"= f32\[({n},{d}|{d},{n})\]\{{[^}}]*\}} copy\(", hlo)
    if rows == "trainer":
        assert not copies, f"table-sized copies in the update: {copies}"
        assert "tpu_custom_call" in hlo
    else:
        assert copies, "the control found no table-sized copy"
