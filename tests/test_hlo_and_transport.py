"""HLO collective parser and transport-curve tests."""
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.transport import GBPS, get_transport
from repro.utils.hlo import collective_bytes, collective_counts

HLO_SAMPLE = """
HloModule jit_step
  %x.1 = bf16[16,128]{1,0} all-gather(%p0), replica_groups={}
  %y = f32[256]{0} all-reduce(%q), to_apply=%add
  %z = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(%a, %b)
  %w = f32[64]{0} reduce-scatter(%c), dimensions={0}
  %cp = u32[] collective-permute(%d), source_target_pairs={{0,1}}
  %ag2 = bf16[4,4]{1,0} all-gather-start(%p1)
  %agd = bf16[4,4]{1,0} all-gather-done(%ag2)
"""


def test_collective_bytes_parses_kinds():
    out = collective_bytes(HLO_SAMPLE)
    assert out["all-gather"] == 16 * 128 * 2 + 4 * 4 * 2   # incl. -start
    assert out["all-reduce"] == 256 * 4
    assert out["all-to-all"] == 2 * 64 * 4
    assert out["reduce-scatter"] == 64 * 4
    assert out["collective-permute"] == 4


def test_collective_counts():
    out = collective_counts(HLO_SAMPLE)
    assert out["all-gather"] == 2          # plain + -start (done skipped)
    assert out["all-reduce"] == 1


def test_done_ops_not_double_counted():
    text = "%a = bf16[8]{0} all-gather-start(%x)\n%b = bf16[8]{0} all-gather-done(%a)"
    assert collective_counts(text)["all-gather"] == 1


# ---------------------------------------------------------------------------
# transport curves
# ---------------------------------------------------------------------------

def test_ideal_transport():
    t = get_transport("ideal")
    assert t.effective(100 * GBPS) == 100 * GBPS


def test_horovod_transport_calibration():
    t = get_transport("horovod_tcp")
    # paper Fig. 4: 1 Gbps fully utilized; 100 Gbps capped below 32 Gbps
    assert t.utilization(1 * GBPS) > 0.95
    assert t.effective(100 * GBPS) < 32 * GBPS
    # plateau: going 25 -> 100 Gbps gains little
    assert t.effective(100 * GBPS) / t.effective(25 * GBPS) < 1.5


@settings(max_examples=30, deadline=None)
@given(bw=st.floats(0.1, 400))
def test_transport_effective_never_exceeds_physical(bw):
    for name in ("ideal", "horovod_tcp", "tpu_ici"):
        t = get_transport(name)
        assert t.effective(bw * GBPS) <= bw * GBPS + 1e-6


@settings(max_examples=30, deadline=None)
@given(bw1=st.floats(0.1, 100), bw2=st.floats(100, 400))
def test_transport_monotone(bw1, bw2):
    for name in ("ideal", "horovod_tcp", "tpu_ici"):
        t = get_transport(name)
        assert t.effective(bw2 * GBPS) >= t.effective(bw1 * GBPS) - 1e-6


# ---------------------------------------------------------------------------
# loop-trip-aware analyzer (repro.utils.hlo.analyze)
# ---------------------------------------------------------------------------

def test_analyze_scales_by_trip_count():
    """A 6-iteration scan of one 64x64 matmul must report ~6x the flops of
    the single-layer cost, with the collectives inside the loop scaled too."""
    import subprocess, sys, json, os
    from pathlib import Path
    script = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json
sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.utils.hlo import analyze

mesh = jax.make_mesh((2, 2), ("data", "model"))
def step(w, x):
    def body(c, wi):
        return jnp.tanh(c @ wi), ()
    c, _ = jax.lax.scan(body, x, w)
    return c.sum()
w = jax.ShapeDtypeStruct((6, 64, 64), jnp.float32)
x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
with mesh:
    c = jax.jit(jax.grad(step),
                in_shardings=(NamedSharding(mesh, P(None, "model", None)),
                              NamedSharding(mesh, P("data", None)))
                ).lower(w, x).compile()
a = analyze(c.as_text())
ca = c.cost_analysis()
if isinstance(ca, (list, tuple)):   # older jax returns one dict per program
    ca = ca[0] if ca else {}
print(json.dumps({"flops": a.flops, "trips": a.while_trips,
                  "coll": a.collective_bytes,
                  "cost": ca.get("flops", 0.0)}))
'''
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", script], cwd=repo, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # fwd dot (2*4*64*32) + bwd dx (2*4*32*64) + bwd dw (2*64*32*4), x6 trips
    assert out["flops"] == pytest.approx(6 * 3 * 2 * 4 * 64 * 32, rel=0.01)
    assert 6 in out["trips"]
    # the fwd TP all-reduce runs 6 times: 6 * (4*64*4B) at minimum
    assert out["coll"].get("all-reduce", 0) >= 6 * 4 * 64 * 4
    # and the trip-aware flops exceed the while-body-once cost_analysis
    assert out["flops"] > out["cost"]


def test_analyze_handles_tuple_while_types():
    from repro.utils.hlo import parse_computations
    txt = """
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  ROOT %t = (s32[], f32[4]) tuple(%gte, %gte2)
}
%cond (p2: (s32[], f32[4])) -> pred[] {
  %p2 = (s32[], f32[4]) parameter(0)
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%gte3, %c), direction=LT
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%init), condition=%cond, body=%body
}
"""
    comps = parse_computations(txt)
    assert {"body", "cond", "main"} <= set(comps)


# ---------------------------------------------------------------------------
# collective provenance (scalar / exchange / SPMD-inserted)
# ---------------------------------------------------------------------------

HLO_PROVENANCE = """
HloModule jit_train_step

%fused_computation.1 (param_0.1: f32[2,64]) -> f32[4,2,64] {
  %param_0.1 = f32[2,64]{1,0} parameter(0)
  ROOT %all-gather.1 = f32[4,2,64]{2,1,0} all-gather(%param_0.1), dimensions={0}, metadata={op_name="jit(train_step)/shard_map/all_gather"}
}

%fused_computation.2 (param_0.2: f32[2,64]) -> f32[4,2,64] {
  %param_0.2 = f32[2,64]{1,0} parameter(0)
  ROOT %all-gather.2 = f32[4,2,64]{2,1,0} all-gather(%param_0.2), dimensions={0}, metadata={op_name="jit(train_step)/shard_map/all_gather"}
}

ENTRY %main (p: f32[8,64]) -> f32[] {
  %p = f32[8,64]{1,0} parameter(0)
  %rs = f32[8,64]{1,0} all-reduce(%p), to_apply=%add
  %f1 = f32[4,2,64]{2,1,0} fusion(%p), kind=kLoop, calls=%fused_computation.1
  %f2 = f32[4,2,64]{2,1,0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %g = bf16[512,64]{1,0} all-reduce-start(%p), to_apply=%add, metadata={op_name="jit(train_step)/transpose(jvp(dot_general))"}
  %gd = bf16[512,64]{1,0} all-reduce-done(%g)
  ROOT %loss = (f32[], f32[]) all-reduce(%p, %p), to_apply=%add, metadata={op_name="jit(train_step)/shard_map/psum"}
}
"""


def test_collective_ops_groups_by_provenance():
    from repro.utils.hlo import collective_ops
    out = collective_ops(HLO_PROVENANCE)
    # the source-less all-reduce is a compiler-rewritten exchange op; the
    # all-gather fused into two consumers counts once
    assert out["exchange"] == {"all-reduce": 1, "all-gather": 1}
    assert out["spmd"] == {"all-reduce": 1}          # async pair, once
    assert out["scalar"] == {"all-reduce": 1}
