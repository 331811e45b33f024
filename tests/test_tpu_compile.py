"""Compile the served retrieval programs for a TPU v5e, at real widths.

Nothing here runs on a chip: the v5e:2x2 topology is *described* and the
TPU compiler refuses what the chip would refuse — a kernel block that
breaks the tiling rule, a program past the device's memory. Every test
compiles in this process (a child could not load the TPU library while
this process holds it), and the topology is described inside a fixture,
never at import, so every test worker collects the same tests.
"""

from __future__ import annotations

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import SCORE_BLOCK
from repro.kernels.mips_topk.kernel import mips_topk_pallas
from repro.retrieval.index import Q_BLOCK, DenseIndex, _block_width, search_program

N_DOCS, DIM = 1_000_000, 768  # a million BERT-base-width passages, f32
N_PADDED = math.ceil(N_DOCS / SCORE_BLOCK) * SCORE_BLOCK


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe here
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k", [3, 5, 10])
@pytest.mark.parametrize("masked", [False, True], ids=["n_valid", "valid_mask"])
def test_mips_topk_compiles_for_v5e(one_chip, k, masked):
    q = jax.ShapeDtypeStruct((Q_BLOCK, DIM), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((N_PADDED, DIM), jnp.float32, sharding=one_chip)
    if masked:
        m = jax.ShapeDtypeStruct((N_PADDED,), jnp.float32, sharding=one_chip)
        fn = jax.jit(lambda q, c, m: mips_topk_pallas(q, c, k, block_n=SCORE_BLOCK, valid_mask=m))
        compiled = fn.lower(q, c, m).compile()
    else:
        fn = jax.jit(lambda q, c: mips_topk_pallas(q, c, k, block_n=SCORE_BLOCK, n_valid=N_DOCS))
        compiled = fn.lower(q, c).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= N_PADDED * DIM * 4


@pytest.mark.parametrize("scorer", ["blocked", "pallas"])
def test_dense_search_program_takes_the_corpus_as_an_argument(one_chip, scorer):
    q = jax.ShapeDtypeStruct((Q_BLOCK, DIM), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((N_PADDED, DIM), jnp.float32, sharding=one_chip)
    lowered = search_program(10, N_DOCS, scorer).lower(c, q)
    assert len(lowered.as_text()) < 200_000  # no corpus-sized constant
    mem = lowered.compile().memory_analysis()
    corpus_bytes = N_PADDED * DIM * 4
    assert corpus_bytes <= mem.argument_size_in_bytes < corpus_bytes * 1.01
    # the program holds no second copy of the corpus
    assert mem.temp_size_in_bytes < corpus_bytes / 10


# the benchmark's corpora: BEIR NQ at 768-d and BEIR HotpotQA at 384-d
_CELL_SHAPES = {"nq-768": (2_681_468, 768), "hotpotqa-384": (5_233_329, 384)}


@pytest.mark.parametrize("k", [3, 5, 10])
@pytest.mark.parametrize("shape", list(_CELL_SHAPES))
def test_served_search_sorts_no_score_block(one_chip, shape, k):
    """The served program selects its top-k by group maxima at the
    benchmark's shapes: no sort in it spans 4096 or more score columns, and
    it holds no copy of the corpus."""
    rows, dim = _CELL_SHAPES[shape]
    padded = rows + (-rows) % _block_width(k)
    q = jax.ShapeDtypeStruct((Q_BLOCK, dim), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((padded, dim), jnp.float32, sharding=one_chip)
    compiled = search_program(k, rows).lower(c, q).compile()
    sorts = re.findall(r"= (.*?) sort\(", compiled.as_text())
    assert sorts  # lax.top_k lowers to sorts on the TPU: the check reads them
    widths = [int(w) for s in sorts for dims in re.findall(r"\[([\d,]+)\]", s)
              for w in dims.split(",")]
    assert max(widths) < 4096, sorts
    corpus_bytes = padded * dim * 4
    assert compiled.memory_analysis().temp_size_in_bytes < corpus_bytes / 10


def test_four_chip_sharded_search_puts_a_quarter_on_each_device(topo):
    n_docs, shards = 6_000_000, 4
    rows_per = math.ceil(n_docs / shards / SCORE_BLOCK) * SCORE_BLOCK
    mesh = Mesh(np.asarray(topo.devices[:shards]), ("data",))
    index = DenseIndex(np.zeros((8, DIM), np.float32), assume_normalized=True)
    fn, n = index.sharded_search_fn(mesh, 10, ("data",), n_valid=n_docs)
    assert n == shards
    c = jax.ShapeDtypeStruct(
        (rows_per * shards, DIM), jnp.float32, sharding=NamedSharding(mesh, P("data", None))
    )
    q = jax.ShapeDtypeStruct((Q_BLOCK, DIM), jnp.float32, sharding=NamedSharding(mesh, P()))
    compiled = fn.lower(c, q).compile()
    per_device = compiled.memory_analysis().argument_size_in_bytes
    quarter = n_docs * DIM * 4 / shards
    assert quarter <= per_device < 1.01 * quarter
    assert "all-gather" in compiled.as_text()  # the on-device top-k merge


@pytest.mark.parametrize("k", [3, 5, 10])
def test_msmarco_sharded_search_fits_each_of_four_chips(topo, k):
    """MS MARCO passage v1 (8,841,823 × 768 f32, 27.2 GB) row-sharded over a
    v5e:2x2 host, as the benchmark's four-chip cell serves it: each device
    holds its 2,210,816 padded rows and no second copy, the top-k is
    selected without a sort of 4096 columns, and the chips' lists meet in
    an all-gather."""
    n_docs, shards = 8_841_823, 4
    bn = _block_width(k)
    rows_per = math.ceil(math.ceil(n_docs / shards) / bn) * bn
    assert rows_per == 2_210_816
    mesh = Mesh(np.asarray(topo.devices[:shards]), ("data",))
    index = DenseIndex(np.zeros((8, DIM), np.float32), assume_normalized=True)
    fn, _ = index.sharded_search_fn(mesh, k, ("data",), n_valid=n_docs)
    c = jax.ShapeDtypeStruct(
        (rows_per * shards, DIM), jnp.float32, sharding=NamedSharding(mesh, P("data", None))
    )
    q = jax.ShapeDtypeStruct((Q_BLOCK, DIM), jnp.float32, sharding=NamedSharding(mesh, P()))
    compiled = fn.lower(c, q).compile()
    mem = compiled.memory_analysis()
    shard_bytes = rows_per * DIM * 4
    assert shard_bytes <= mem.argument_size_in_bytes < shard_bytes * 1.01
    assert mem.temp_size_in_bytes < shard_bytes / 10
    text = compiled.as_text()
    assert "all-gather" in text
    widths = [int(w) for s in re.findall(r"= (.*?) sort\(", text)
              for dims in re.findall(r"\[([\d,]+)\]", s) for w in dims.split(",")]
    assert widths and max(widths) < 4096
