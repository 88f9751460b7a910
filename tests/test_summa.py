"""SUMMA-streamed distributed blocked matmul (``parallel/summa.py``).

What these tests pin, on the tier-1 virtual 4-device mesh (the
``mesh`` marker / ``mesh4`` fixture — 4 of the suite's 8 forced
host-platform CPU devices):

* **byte equality** — the SUMMA result is byte-identical to the
  single-device blocked engine (integer-valued f32 operands make
  every summation order exact, so this is a true bit-for-bit gate);
* **panel staging** — each participant stages ~1/N of the operand
  bytes;
* **knob routing** — ``config.distributed_matmul`` routes
  ``matmul_streamed`` (and ``ops.matmul``) through the engine, off
  keeps the single-device path byte-for-byte;
* **device-cache integration** — SUMMA panels install as
  block-granular entries under the mesh-labelled key; a warm re-run
  stages only the B panels (zero arena reads for A).
"""

import numpy as np
import pytest

from netsdb_tpu import obs
from netsdb_tpu.config import Configuration
from netsdb_tpu.plan import staging
from netsdb_tpu.storage.devcache import DeviceBlockCache
from netsdb_tpu.storage.paged import PagedTensorStore

pytestmark = pytest.mark.mesh


def _int_f32(rng, shape, lo=-8, hi=8):
    """Integer-valued f32: products and partial sums are exact in
    f32 at these magnitudes, so ANY accumulation order is bit-equal —
    the byte-equality gate is meaningful, not luck."""
    return rng.integers(lo, hi, size=shape).astype(np.float32)


def _store(tmp_path, rows=1024, k=96, cols=40, row_block=128, **cfg):
    config = Configuration(root_dir=str(tmp_path / "s"),
                           page_size_bytes=64 * 1024, **cfg)
    pts = PagedTensorStore(config, force_python=True)
    rng = np.random.default_rng(7)
    m = _int_f32(rng, (rows, k))
    rhs = _int_f32(rng, (k, cols))
    pts.put("m", m, row_block=row_block)
    return pts, m, rhs


def test_summa_byte_equal_single_device_engine(tmp_path, mesh4):
    from netsdb_tpu.parallel.summa import summa_matmul_streamed

    pts, m, rhs = _store(tmp_path)
    base = pts.matmul_streamed("m", rhs)  # single-device blocked engine
    assert np.array_equal(base, m @ rhs)
    out = summa_matmul_streamed(pts, "m", rhs,
                                devices=list(mesh4.devices.flat))
    assert out.tobytes() == base.tobytes()
    assert staging.active_count() == 0


def test_summa_ragged_tail_and_vector_rhs(tmp_path, mesh4):
    from netsdb_tpu.parallel.summa import summa_matmul_streamed

    # 9 blocks over 4 participants (uneven panels + ragged last block)
    pts, m, rhs = _store(tmp_path, rows=1100, k=50, row_block=128)
    devs = list(mesh4.devices.flat)
    base = pts.matmul_streamed("m", rhs)
    out = summa_matmul_streamed(pts, "m", rhs, devices=devs)
    assert out.tobytes() == base.tobytes()
    vec = np.arange(50, dtype=np.float32)
    got = summa_matmul_streamed(pts, "m", vec, devices=devs)
    assert got.shape == (1100,)
    assert np.array_equal(got, m @ vec)


def test_summa_per_host_staged_fraction(tmp_path, mesh4):
    """The panel-staging proof at test scale: blocks already
    bucket-shaped and dealt evenly, so each participant stages
    ~1/N of A plus one B panel — never the whole operands."""
    from netsdb_tpu.parallel.summa import summa_matmul_streamed

    pts, m, rhs = _store(tmp_path, rows=2048, k=64, cols=32,
                         row_block=256)  # 8 blocks / 4 participants
    stats = {}
    out = summa_matmul_streamed(pts, "m", rhs,
                                devices=list(mesh4.devices.flat),
                                stats_out=stats)
    assert np.array_equal(out, m @ rhs)
    assert stats["participants"] == 4
    assert stats["rounds"] == 2
    assert stats["panel_bcasts"] == 8  # N per round
    per_host = stats["staged_bytes_per_participant"]
    assert set(per_host) == {0, 1, 2, 3}
    ideal = stats["operand_bytes"] / 4
    for d, nbytes in per_host.items():
        # 1/N of A (+ its B panel); 35% headroom for padding
        assert nbytes <= ideal * 1.35, (d, nbytes, ideal)
    assert staging.active_count() == 0


def test_distributed_matmul_knob_routes_streamed(tmp_path, mesh4):
    rounds0 = obs.REGISTRY.counter("summa.rounds").value
    pts, m, rhs = _store(tmp_path, distributed_matmul=True,
                         summa_participants=4)
    out = pts.matmul_streamed("m", rhs)
    assert obs.REGISTRY.counter("summa.rounds").value > rounds0
    # knob off: the single-device engine, byte-for-byte
    pts2, m2, rhs2 = _store(tmp_path / "off", distributed_matmul=False)
    base = pts2.matmul_streamed("m", rhs)
    assert out.tobytes() == base.tobytes()


def test_summa_warm_rerun_serves_panels_from_devcache(tmp_path, mesh4):
    """A second SUMMA run under the same mesh serves every A panel
    from the block-granular device cache: zero arena reads, zero A
    bytes staged — only the B panels re-upload."""
    from netsdb_tpu.parallel.summa import summa_matmul_streamed

    pts, m, rhs = _store(tmp_path, rows=2048, k=64, cols=32,
                         row_block=256)
    devs = list(mesh4.devices.flat)
    cache = DeviceBlockCache(64 * 1024 * 1024, partial=True)
    cold, warm = {}, {}
    o1 = summa_matmul_streamed(pts, "m", rhs, devices=devs,
                               cache=cache, cache_scope="d:m",
                               stats_out=cold)
    chunks0 = obs.REGISTRY.counter("staging.chunks").value
    o2 = summa_matmul_streamed(pts, "m", rhs, devices=devs,
                               cache=cache, cache_scope="d:m",
                               stats_out=warm)
    assert o2.tobytes() == o1.tobytes()
    # warm: no staged chunks at all (the B panels upload outside the
    # staging pipeline), every A block a partial hit
    assert obs.REGISTRY.counter("staging.chunks").value == chunks0
    rhs_bytes = sum(cold["staged_bytes_per_participant"].values()) \
        - warm["staged_bytes_total"]
    assert rhs_bytes > 0  # warm staged strictly less: only B panels
    st = cache.stats()
    assert st["partial_hits"] >= pts.num_blocks("m")
    assert st["hits"] >= 1  # full-coverage consult
    assert staging.active_count() == 0


def test_summa_mesh_label_keys_never_alias(tmp_path, mesh4):
    """Cached panels are sharding-keyed: a run under a DIFFERENT
    participant count must miss (its panels live on other devices)."""
    from netsdb_tpu.parallel.summa import summa_matmul_streamed

    pts, m, rhs = _store(tmp_path, rows=2048, k=64, cols=32,
                         row_block=256)
    devs = list(mesh4.devices.flat)
    cache = DeviceBlockCache(64 * 1024 * 1024, partial=True)
    summa_matmul_streamed(pts, "m", rhs, devices=devs, cache=cache,
                          cache_scope="d:m")
    st0 = cache.stats()
    out = summa_matmul_streamed(pts, "m", rhs, devices=devs[:2],
                                cache=cache, cache_scope="d:m")
    assert np.array_equal(out, m @ rhs)
    st1 = cache.stats()
    assert st1["misses"] == st0["misses"] + 1  # no stale-layout hit
    # a DIFFERENT device set of the SAME size keys apart too: cached
    # panels are committed to specific physical devices
    import jax

    all_devs = jax.devices()
    if len(all_devs) >= 8:
        out2 = summa_matmul_streamed(pts, "m", rhs,
                                     devices=all_devs[4:8],
                                     cache=cache, cache_scope="d:m")
        assert np.array_equal(out2, m @ rhs)
        assert cache.stats()["misses"] == st1["misses"] + 1
    assert staging.active_count() == 0


def test_ops_matmul_distributed_matches_resident(mesh4):
    import jax

    from netsdb_tpu.core.blocked import BlockedTensor
    from netsdb_tpu.ops.matmul import matmul

    rng = np.random.default_rng(3)
    a = BlockedTensor.from_dense(_int_f32(rng, (300, 70)), (128, 128))
    b = BlockedTensor.from_dense(_int_f32(rng, (70, 90)), (128, 128))
    base = matmul(a, b, distributed=False)
    out = matmul(a, b, distributed=True)
    assert out.shape == base.shape
    assert np.array_equal(np.asarray(out.to_dense()),
                          np.asarray(base.to_dense()))
    assert isinstance(out.data, jax.Array)


def test_summa_counters_catalogued():
    """Every summa.*/reshard.* registry counter the engine ticks must
    be catalogued (the drift gate covers docs; this pins the exporter
    surface for the NEW families specifically)."""
    from netsdb_tpu.obs.export import CATALOG

    names = set(CATALOG)
    for name in ("summa.rounds", "summa.panel_bcasts",
                 "summa.panel_bytes", "summa.staged_bytes",
                 "reshard.plans", "reshard.steps",
                 "reshard.blocks_moved", "reshard.bytes_moved"):
        assert name in names, name


# --- 2-d processor grid (PR 17) ---------------------------------------

def test_summa_grid_byte_equal_single_device_engine(tmp_path, mesh4):
    """The 2-d grid engine (2112.09017 §III) matches the single-device
    blocked engine byte for byte — same f32 HIGHEST contraction, the
    dual-broadcast steps only reassociate exactly."""
    from netsdb_tpu.parallel.summa import summa_grid_matmul_streamed

    pts, m, rhs = _store(tmp_path)
    base = pts.matmul_streamed("m", rhs)
    out = summa_grid_matmul_streamed(pts, "m", rhs,
                                     devices=list(mesh4.devices.flat),
                                     grid=(2, 2))
    assert out.tobytes() == base.tobytes()
    assert staging.active_count() == 0


def test_summa_grid_staged_fraction_and_counters(tmp_path, mesh4):
    """Each grid device stages ~1/(pr*pc) of A — the both-dims-
    exceed-one-host layout's defining property — and the grid counter
    family ticks."""
    from netsdb_tpu.parallel.summa import summa_grid_matmul_streamed

    rounds0 = obs.REGISTRY.counter("summa.grid_rounds").value
    pts, m, rhs = _store(tmp_path, rows=2048, k=64, cols=32,
                         row_block=256)  # 8 blocks / 2 grid rows
    stats = {}
    out = summa_grid_matmul_streamed(pts, "m", rhs,
                                     devices=list(mesh4.devices.flat),
                                     grid=(2, 2), stats_out=stats)
    assert np.array_equal(out, m @ rhs)
    assert stats["grid"] == (2, 2) and stats["participants"] == 4
    assert stats["rounds"] == 4  # pr blocks per round
    a_bytes = m.nbytes
    for d, nbytes in stats["staged_bytes_per_participant"].items():
        # 1/4 of A split as (row-deal over pr) x (column-split over
        # pc); 60% headroom for contraction padding to k_pad
        assert nbytes <= a_bytes / 4 * 1.6, (d, nbytes)
    assert obs.REGISTRY.counter("summa.grid_rounds").value == rounds0 + 4
    assert obs.REGISTRY.counter("summa.grid_steps").value > 0
    assert staging.active_count() == 0


def test_summa_grid_knob_routes_and_label_keys(tmp_path, mesh4):
    """config.summa_grid="2x2" routes matmul_streamed through the grid
    engine; the grid label never aliases the 1-d label for the same
    scope (different layouts = different cached-panel homes)."""
    from netsdb_tpu.parallel.summa import grid_label, grid_shape, mesh_label

    g0 = obs.REGISTRY.counter("summa.grid_rounds").value
    pts, m, rhs = _store(tmp_path, distributed_matmul=True,
                         summa_participants=4, summa_grid="2x2")
    out = pts.matmul_streamed("m", rhs)
    assert obs.REGISTRY.counter("summa.grid_rounds").value > g0
    assert np.array_equal(out, m @ rhs)

    devs = list(mesh4.devices.flat)
    assert grid_label(devs, 2, 2) != mesh_label("data", devs)
    assert grid_label(devs, 2, 2) != grid_label(devs, 1, 4)

    class _C:
        summa_grid = "2x2"

    assert grid_shape(_C(), 4) == (2, 2)
    assert grid_shape(_C(), 3) is None  # grid does not fit
    _C.summa_grid = None
    assert grid_shape(_C(), 4) is None
    _C.summa_grid = "2xbogus"
    with pytest.raises(ValueError, match="PRxPC"):
        grid_shape(_C(), 4)


def test_summa_grid_warm_rerun_zero_arena_reads(tmp_path, mesh4):
    """A warm grid re-run serves every A tile from the device cache:
    zero staged chunks (no arena reads), only the B tiles re-upload —
    byte-equal output."""
    from netsdb_tpu.parallel.summa import summa_grid_matmul_streamed

    pts, m, rhs = _store(tmp_path, rows=2048, k=64, cols=32,
                         row_block=256)
    devs = list(mesh4.devices.flat)
    cache = DeviceBlockCache(64 * 1024 * 1024, partial=True)
    o1 = summa_grid_matmul_streamed(pts, "m", rhs, devices=devs,
                                    grid=(2, 2), cache=cache,
                                    cache_scope="d:m")
    chunks0 = obs.REGISTRY.counter("staging.chunks").value
    warm = {}
    o2 = summa_grid_matmul_streamed(pts, "m", rhs, devices=devs,
                                    grid=(2, 2), cache=cache,
                                    cache_scope="d:m", stats_out=warm)
    assert o2.tobytes() == o1.tobytes()
    assert obs.REGISTRY.counter("staging.chunks").value == chunks0
    # nothing of A re-staged: the warm total is exactly one B upload
    assert warm["staged_bytes_total"] <= rhs.nbytes
    assert staging.active_count() == 0


def test_summa_grid_counters_catalogued():
    from netsdb_tpu.obs.export import CATALOG

    for name in ("summa.grid_rounds", "summa.grid_steps",
                 "summa.grid_panel_bcasts", "summa.grid_staged_bytes",
                 "models.deploys", "models.batches_scored",
                 "models.rows_scored", "serve.client.routed_ingests",
                 "shard.analyze_fanouts"):
        assert name in CATALOG, name


def test_ff_plan_leg_routes_tensor_stream_through_summa(tmp_path, mesh4):
    """Tentpole (a) pinned: a COMPILED PLAN's tensor-fold stream (FF
    inference over paged weights) routes through SUMMA when
    ``distributed_matmul`` is on — byte-equal to the knob-off run,
    summa.rounds ticks, and the 2-d grid knob routes the same stream
    through the grid engine."""
    from netsdb_tpu.client import Client
    from netsdb_tpu.models.ff import FFModel

    rng = np.random.default_rng(5)
    F, H, L = 96, 128, 10
    w1, b1 = _int_f32(rng, (H, F), -2, 2), _int_f32(rng, (H,), -2, 2)
    wo, bo = _int_f32(rng, (L, H), -2, 2), _int_f32(rng, (L,), -2, 2)
    x = _int_f32(rng, (32, F), -2, 2)

    def _run(tag, **cfg):
        c = Client(Configuration(root_dir=str(tmp_path / tag),
                                 page_size_bytes=4096,
                                 page_pool_bytes=16384, **cfg))
        m = FFModel(db="ff", block=(32, 32))
        m.setup(c, storages={"w1": "paged", "wo": "paged"})
        m.load_weights(c, w1, b1, wo, bo)
        m.load_inputs(c, x)
        return np.asarray(m.inference(c).to_dense())

    base = _run("base")
    r0 = obs.REGISTRY.counter("summa.rounds").value
    dist = _run("dist", distributed_matmul=True, summa_participants=4)
    assert obs.REGISTRY.counter("summa.rounds").value > r0
    np.testing.assert_array_equal(base, dist)
    g0 = obs.REGISTRY.counter("summa.grid_rounds").value
    grid = _run("grid", distributed_matmul=True, summa_participants=4,
                summa_grid="2x2")
    assert obs.REGISTRY.counter("summa.grid_rounds").value > g0
    np.testing.assert_array_equal(base, grid)
