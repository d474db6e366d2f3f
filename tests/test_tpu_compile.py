"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, primitives Mosaic cannot lower, programs that do not fit
HBM, kernels GSPMD cannot partition.  These tests compile the serving
path's programs at a deployment's widths — the fraud view at 131,072 keys,
ring capacity 256, 512 buckets — and read the compiler's own memory
analysis and HLO.  Nothing runs; no chip is needed.

The topology is described inside a module fixture (never at import): only
the worker that runs this file loads the TPU compiler library.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import OnlineFeatureStore, ShardedOnlineStore
from repro.core.layout import plan_layout
from repro.core.online import state_init
from repro.kernels.ingest.ops import fused_ingest_apply
from repro.kernels.route.ops import _ROUTE_PALLAS_MAX_ROWS, _route_rank
from repro.kernels.window_agg.ops import FOLD_TILE_ROWS, _fold_levels
from repro.scenarios import fraud_view, sharded_view

K, C, NB, BS = 131_072, 256, 512, 64
HBM_LIMIT = 15.75 * 2**30  # a v5e's 16 GiB less the runtime's reserve
GiB = 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("shard",))


def _abstract(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _nbytes(tree):
    return sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree)
    )


def _fraud_state():
    """The fraud view's single-chip state at K keys, as shapes only."""
    lay = plan_layout(
        [fraud_view()], num_keys=K, capacity=C, num_buckets=NB,
        bucket_size=BS,
    )
    return jax.eval_shape(lambda: state_init(lay))


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_ingest_program_fits_at_real_key_count(one_chip, impl):
    """The store's ingest (both impls) compiles with donated state whose
    temporaries stay within the state's own size; the kernel path updates
    the state in place (no whole-state relayout copy)."""
    st = _fraud_state()
    F = st.ring.vals.shape[0]
    n = 4096
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = _abstract(
        (st.ring.ts, st.ring.vals, st.ring.cursor,
         st.bagg.stats, st.bagg.bitmap, st.bagg.bucket),
        one_chip,
    )
    fn = jax.jit(
        functools.partial(fused_ingest_apply, bucket_size=BS, impl=impl),
        donate_argnums=tuple(range(6)),
    )
    compiled = fn.lower(
        *state, sd((n,), jnp.int32), sd((n,), jnp.int32),
        sd((n, F), jnp.float32),
    ).compile()
    ma = compiled.memory_analysis()
    state_b = _nbytes(state)
    assert state_b > 3.5 * GiB
    assert ma.temp_size_in_bytes <= state_b
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_LIMIT
    assert ma.alias_size_in_bytes >= state_b - 2**20  # donated in place
    if impl == "pallas":
        assert "tpu_custom_call" in compiled.as_text()
        assert ma.temp_size_in_bytes < 0.01 * state_b


def _hlo_dims(hlo: str) -> dict:
    """Result dims of every array-valued HLO instruction, by name."""
    return {
        m.group(1): tuple(int(d) for d in m.group(2).split(",") if d)
        for m in re.finditer(r"%([\w.\-]+) = \w+\[([\d,]*)\]", hlo)
    }


def _cell_gathers_of_state(hlo: str) -> list:
    """Gathers that read a (..., K, C) or (..., K, NB) state operand one
    cell at a time: slice size 1 in its minor (slot) axis.  The (K,)
    cursor has no slot axis and is not one."""
    dims = _hlo_dims(hlo)
    out = []
    for m in re.finditer(
        r"gather\(%([\w.\-]+), [^\n]*?slice_sizes=\{([\d,]*)\}", hlo
    ):
        d = dims.get(m.group(1), ())
        sizes = [int(x) for x in m.group(2).split(",") if x]
        if len(d) >= 2 and d[-2] == K and d[-1] in (C, NB) and sizes[-1] == 1:
            out.append(m.group(0))
    return out


def _relayouts_of_state(hlo: str) -> list:
    """copy / transpose instructions whose result is as large as one
    (K, C) state plane: a relayout of per-key state."""
    out = []
    for line in hlo.splitlines():
        m = re.search(
            r"= \w+\[([\d,]*)\]\S* (copy|copy-start|transpose)\(", line
        )
        if m and int(np.prod([int(d) for d in m.group(1).split(",") if d])) \
                >= K * C:
            out.append(line.strip())
    return out


def test_preagg_query_program_fits_at_real_key_count(one_chip):
    """The store's pre-aggregated query program, traced by a store of the
    same layout at a small key count and compiled against the 131,072-key
    state: it reads whole per-key rows, so it needs almost no temporaries,
    never relayouts the state, and has no cell-at-a-time gather of it."""
    st = _fraud_state()
    store = OnlineFeatureStore(
        fraud_view(), num_keys=64, capacity=C, num_buckets=NB,
        bucket_size=BS,
    )
    q = 512
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = store._query_preagg_fn.lower(
        _abstract(st, one_chip), sd((q,), jnp.int32), sd((q,), jnp.int32),
        sd((q, store.num_lanes), jnp.float32), (), sd((q,), jnp.int32),
    ).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes < 64 * 2**20
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_LIMIT
    hlo = compiled.as_text()
    assert "gather(" in hlo
    assert _relayouts_of_state(hlo) == []
    assert _cell_gathers_of_state(hlo) == []


@pytest.mark.parametrize("n", [4096, _ROUTE_PALLAS_MAX_ROWS])
def test_route_rank_kernel_compiles(one_chip, n):
    x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    fn = jax.jit(functools.partial(_route_rank, num_shards=4, impl="pallas"))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()


def test_fold_levels_kernel_compiles(one_chip):
    n = 1 << 20
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    fn = jax.jit(functools.partial(
        _fold_levels, op="max", impl="pallas", interpret=False,
        tile_rows=FOLD_TILE_ROWS,
    ))
    assert "tpu_custom_call" in fn.lower(x, seg).compile().as_text()


def _sharded_store(mesh4):
    """A 4-shard store of the sharded view, its programs re-targeted at
    the described mesh, and its state at 4 x 131,072 keys as shapes."""
    kw = dict(
        capacity=C, num_buckets=NB, bucket_size=BS, num_shards=4,
        secondary_num_keys={"merchants": 4096},
    )
    store = ShardedOnlineStore(sharded_view(), num_keys=64, **kw)
    store.mesh = mesh4
    store.sharding = NamedSharding(mesh4, P("shard"))
    store.ingest_impl = "pallas"
    store._build_fns()
    lay = plan_layout([sharded_view()], num_keys=4 * K, **kw)
    single = jax.eval_shape(lambda: state_init(lay))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            (4,) + a.shape, a.dtype, sharding=store.sharding
        ),
        single,
    )
    return store, state


def _gathers_of_state(hlo: str, state) -> list:
    """all-gather instructions whose result is as large as a state leaf."""
    big = min(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(state))
    out = []
    for line in hlo.splitlines():
        if "all-gather" not in line or "=" not in line:
            continue
        m = re.search(r"= \w+\[([\d,]*)\]", line)
        dims = [int(d) for d in m.group(1).split(",") if d] if m else []
        if int(np.prod(dims)) >= big:
            out.append(line.strip())
    return out


def test_sharded_ingest_keeps_state_on_its_shard(mesh4):
    """The vmapped per-shard ingest (Pallas kernel under shard_map)
    compiles on a 4-chip mesh and never all-gathers ring or bucket state."""
    store, state = _sharded_store(mesh4)
    b = 1024
    sd = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=store.sharding
    )
    compiled = store._ingest_fn.lower(
        state, sd((4, b), jnp.int32), sd((4, b), jnp.int32),
        sd((4, b, store.num_lanes), jnp.float32),
    ).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= HBM_LIMIT


def test_sharded_route_query_keeps_state_on_its_shard(mesh4, monkeypatch):
    """The fused route+query program (route kernel + vmapped per-shard
    query) compiles on a 4-chip mesh with no all-gather of state, and its
    per-shard query reads whole rows as the one-chip program does."""
    import repro.kernels.route.ops as rops

    store, state = _sharded_store(mesh4)
    m = 1024
    fn = store._route_query_fn("preagg", None, store._route_bucket(m), 1)
    rep = NamedSharding(mesh4, P())
    rd = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    jks = tuple(rd((m,), jnp.int32) for _ in store._join_cols)
    # route_rank resolves impl="auto" from the backend: steer it to the
    # TPU branch for this compile
    monkeypatch.setattr(rops.jax, "default_backend", lambda: "tpu")
    lowered = fn.lower(
        state, rd((m,), jnp.int32), rd((m,), jnp.int32),
        rd((m, store.num_lanes), jnp.float32), jks, rd((m,), jnp.int32),
        rd((m,), jnp.bool_),
    )
    monkeypatch.undo()
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo
    assert _gathers_of_state(hlo, state) == []
    assert _relayouts_of_state(hlo) == []
    assert _cell_gathers_of_state(hlo) == []
