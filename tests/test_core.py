"""Core library: chunk scheduler, dataflow pipeline, roofline, HLO parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core import hlo as H
from repro.core import roofline as R
from repro.core.chunking import ChunkScheduler, overlap_model
from repro.core.dataflow import Pipeline, Stage, pipeline_model


# --- chunk scheduler -------------------------------------------------------

def test_chunk_scheduler_results_identical():
    kernel = jax.jit(lambda x: jnp.tanh(x) @ x.T)
    chunks = [np.random.default_rng(i).normal(size=(32, 32)).astype(np.float32)
              for i in range(12)]
    s = ChunkScheduler(kernel, depth=4)
    a = s.run_serial(chunks)
    b = s.run_overlapped(chunks)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-6)


@settings(max_examples=50, deadline=None)
@given(n=st.integers(1, 64), depth=st.integers(1, 8))
def test_chunk_scheduler_order_preserved(n, depth):
    kernel = jax.jit(lambda x: x + 1.0)
    chunks = [np.full((2, 2), i, np.float32) for i in range(n)]
    out = ChunkScheduler(kernel, depth=depth).run_overlapped(chunks)
    for i, o in enumerate(out):
        assert float(o[0, 0]) == i + 1.0


@settings(max_examples=100, deadline=None)
@given(total=st.floats(1e6, 1e12), compute=st.floats(1e-4, 10.0),
       bw=st.floats(1e9, 1e12), n=st.integers(1, 256))
def test_overlap_model_invariants(total, compute, bw, n):
    m = overlap_model(total, compute, bw, n)
    assert m["overlapped_s"] <= m["serial_s"] + 1e-9
    assert m["speedup"] >= 1.0 - 1e-9
    assert 0.0 <= m["dma_overhead_overlapped"] <= 1.0 + 1e-9


# --- dataflow pipeline -----------------------------------------------------

def test_pipeline_thread_correctness():
    stages = [Stage("load", lambda x: x * 2),
              Stage("prep", lambda x: x + 1),
              Stage("compute", lambda x: x ** 2),
              Stage("store", lambda x: x - 3)]
    out = Pipeline(stages).run(list(range(50)))
    expect = [((i * 2 + 1) ** 2 - 3) for i in range(50)]
    assert out == expect


@settings(max_examples=100, deadline=None)
@given(stage_times=st.lists(st.floats(1e-4, 1.0), min_size=2, max_size=6),
       n=st.integers(1, 1000))
def test_pipeline_model_bounds(stage_times, n):
    stages = {f"s{i}": t for i, t in enumerate(stage_times)}
    m = pipeline_model(stages, n)
    assert m["pipelined_s"] <= m["serial_s"] + 1e-9
    assert m["speedup"] <= len(stage_times) + 1e-9


def test_pipeline_model_fig4_shape():
    """Dataflow region keeps pipelines filled: compute share rises."""
    stages = {"load": 3.0, "prepare": 0.5, "compute": 1.0, "store": 2.0}
    serial = pipeline_model(stages, 100, overlapped=False)
    flow = pipeline_model(stages, 100)
    assert flow["compute_share"] > serial["compute_share"]
    assert flow["bottleneck"] == "load"


# --- roofline --------------------------------------------------------------

def test_roofline_terms():
    t = R.RooflineTerms(flops_per_dev=197e12, hbm_bytes_per_dev=819e9,
                        ici_wire_bytes=0.0, dcn_wire_bytes=0.0, n_chips=256,
                        model_flops_global=197e12 * 256)
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 1.0) < 1e-9
    assert t.bound in ("compute", "memory")
    assert abs(t.mfu - 1.0) < 1e-6


@settings(max_examples=100, deadline=None)
@given(c1=st.floats(0, 1e15), c2=st.floats(0, 1e15), n=st.integers(1, 128))
def test_differential_costing(c1, c2, n):
    lo, hi = min(c1, c2), max(c1, c2)
    total = R.differential({"f": lo}, {"f": hi}, n, "f")
    per_layer = hi - lo
    assert total >= n * per_layer - 1e-6
    # exact on affine costs: c(n) = const + n*per_layer
    const = 7.0
    t2 = R.differential({"f": const + per_layer}, {"f": const + 2 * per_layer},
                        n, "f")
    assert abs(t2 - (const + n * per_layer)) < max(1e-6 * max(t2, 1), 1e-6)


# --- HLO parser ------------------------------------------------------------

SAMPLE = """
  %all-reduce.1 = f32[1024,512]{1,0} all-reduce(f32[1024,512]{1,0} %x), replica_groups=[16,16]<=[256], to_apply=%add
  %all-gather.3 = bf16[64,2048]{1,0} all-gather(bf16[64,128]{1,0} %y), replica_groups=[16,16]<=[256], dimensions={1}
  %collective-permute.2 = f32[8,8]{1,0} collective-permute(f32[8,8]{1,0} %z), source_target_pairs={{0,1},{1,0}}
  %reduce-scatter.9 = f32[64,32]{1,0} reduce-scatter(f32[64,512]{1,0} %w), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={1}
"""


def test_parse_collectives():
    ops = H.parse_collectives(SAMPLE, pod_size=256)
    kinds = {o.kind for o in ops}
    assert kinds == {"all-reduce", "all-gather", "collective-permute",
                     "reduce-scatter"}
    ar = next(o for o in ops if o.kind == "all-reduce")
    assert ar.out_bytes == 1024 * 512 * 4
    assert ar.group_size == 16
    assert ar.wire_bytes == pytest.approx(2 * 15 / 16 * ar.out_bytes)
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.out_bytes == 64 * 2048 * 2
    rs = next(o for o in ops if o.kind == "reduce-scatter")
    assert rs.wire_bytes == pytest.approx(15 * 64 * 32 * 4)


def test_parse_real_compiled_module():
    """End-to-end: sharded matmul over a small mesh yields collectives."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, sys
        sys.path.insert(0, "src")
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import hlo as H
        mesh = jax.make_mesh((8,), ("model",))
        s = NamedSharding(mesh, P(None, "model"))
        f = lambda a, b: (a @ b).sum()
        a = jax.ShapeDtypeStruct((128, 256), jnp.float32)
        b = jax.ShapeDtypeStruct((256, 512), jnp.float32)
        comp = jax.jit(f, in_shardings=(None, s)).lower(a, b).compile()
        ops = H.parse_collectives(comp.as_text(), pod_size=8)
        assert len(ops) >= 1, comp.as_text()[:2000]
        print("OK", len(ops))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=".", timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
