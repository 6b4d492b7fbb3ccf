"""Mesh/collectives/ring-attention/TP tests on the virtual 8-device CPU mesh
(SURVEY.md section 4 'Device tests' tier)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ai_rtc_agent_tpu.parallel import mesh as M
from ai_rtc_agent_tpu.parallel import ring_attention as RA
from ai_rtc_agent_tpu.parallel import sharding as SH


def test_eight_virtual_devices():
    assert len(jax.devices()) == 8


def test_make_mesh_shapes():
    m = M.make_mesh(dp=2, tp=2, sp=2)
    assert m.shape == {"dp": 2, "tp": 2, "sp": 2}
    m2 = M.auto_mesh(prefer="sp")
    assert m2.shape["sp"] == 8
    with pytest.raises(ValueError):
        M.make_mesh(dp=16)


def test_session_axis_rules_and_knobs(monkeypatch):
    """ISSUE 12 units: the session-axis sharding recipe (the dp
    scheduler's) and the MESH_SHAPE/BATCHSCHED_DP knob
    parsing — all compile-free."""
    from ai_rtc_agent_tpu.utils import env

    m = M.make_mesh(dp=4)
    assert SH.session_axis_spec(m) == P("dp")
    repl, row = SH.session_shardings(m)
    assert repl.spec == P() and row.spec == P("dp")
    devs = SH.dp_devices(m)
    assert len(devs) == 4 and len(set(devs)) == 4
    # shard d of a leading-axis sharded array lives on dp_devices[d]
    arr = jax.device_put(jnp.arange(8.0), row)
    by_start = {
        (s.index[0].start or 0): next(iter(s.data.devices()))
        for s in arr.addressable_shards
    }
    assert [by_start[i * 2] for i in range(4)] == devs
    # a trivial axis replicates (the single-device scheduler unchanged)
    assert SH.session_axis_spec(M.make_mesh(tp=2)) == P()

    # knob parsing: MESH_SHAPE feeds dp when BATCHSCHED_DP is unset
    monkeypatch.delenv("BATCHSCHED_DP", raising=False)
    monkeypatch.setenv("MESH_SHAPE", "8,1,1")
    assert env.mesh_shape() == (8, 1, 1)
    assert env.batchsched_dp() == 8
    monkeypatch.setenv("MESH_SHAPE", "4x2")
    assert env.mesh_shape() == (4, 2, 1)
    monkeypatch.setenv("BATCHSCHED_DP", "2")
    assert env.batchsched_dp() == 2  # explicit knob wins
    # explicit 0 is the per-box kill-switch even under a fleet MESH_SHAPE
    monkeypatch.setenv("MESH_SHAPE", "8,1,1")
    monkeypatch.setenv("BATCHSCHED_DP", "0")
    assert env.batchsched_dp() == 1
    monkeypatch.delenv("MESH_SHAPE")
    assert env.batchsched_dp() == 1  # off -> single-device
    monkeypatch.setenv("MESH_SHAPE", "bogus")
    with pytest.raises(ValueError):
        env.mesh_shape()
    monkeypatch.setenv("MESH_SHAPE", "1,2,3,4")
    with pytest.raises(ValueError):
        env.mesh_shape()


def test_collectives_in_shard_map(rng):
    m = M.make_mesh(dp=8)
    x = jnp.arange(8.0)

    f = jax.shard_map(
        lambda v: jax.lax.psum(v, axis_name="dp"),
        mesh=m,
        in_specs=P("dp"),
        out_specs=P("dp"),
    )
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.full(8, x.sum()))

    def ring_shift(v):
        n = jax.lax.axis_size("dp")
        return jax.lax.ppermute(
            v, axis_name="dp", perm=[(i, (i + 1) % n) for i in range(n)]
        )

    g = jax.shard_map(
        ring_shift,
        mesh=m,
        in_specs=P("dp"),
        out_specs=P("dp"),
    )
    np.testing.assert_allclose(np.asarray(g(x)), np.roll(np.arange(8.0), 1))


@pytest.mark.parametrize("n_sp", [2, 4, 8])
def test_ring_attention_matches_dense(rng, n_sp):
    m = M.make_mesh(sp=n_sp)
    B, L, H, D = 2, 32, 4, 8
    q = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    want = np.asarray(RA.dense_reference(q, k, v))
    got = np.asarray(RA.ring_attention(q, k, v, m))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_ulysses_attention_matches_dense(rng):
    m = M.make_mesh(sp=4)
    B, L, H, D = 1, 16, 4, 8  # H divisible by sp
    q = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    want = np.asarray(RA.dense_reference(q, k, v))
    got = np.asarray(RA.ulysses_attention(q, k, v, m))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_tp_param_shardings_rules():
    m = M.make_mesh(tp=8)
    params = {
        "attn1": {"to_q": {"kernel": jnp.zeros((64, 64))}},
        "ff": {"out": {"kernel": jnp.zeros((64, 64)), "bias": jnp.zeros((64,))}},
        "norm1": {"scale": jnp.zeros((64,)), "bias": jnp.zeros((64,))},
        "odd": {"to_q": {"kernel": jnp.zeros((3, 5))}},  # indivisible
    }
    sh = SH.param_shardings(m, params)
    assert sh["attn1"]["to_q"]["kernel"].spec == P(None, "tp")  # column
    assert sh["ff"]["out"]["kernel"].spec == P("tp", None)  # row
    assert sh["norm1"]["scale"].spec == P()  # replicated
    assert sh["odd"]["to_q"]["kernel"].spec == P(None, None)  # fallback


def test_tp_sharded_unet_forward_matches_single(rng):
    """The TP-sharded UNet must compute the SAME function."""
    from ai_rtc_agent_tpu.models import unet as U

    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(rng.standard_normal((1, 8, 8, 4)).astype(np.float32))
    ctx = jnp.asarray(rng.standard_normal((1, 7, 32)).astype(np.float32))
    t = jnp.array([42])
    want = np.asarray(U.apply_unet(params, x, t, ctx, cfg))

    m = M.make_mesh(tp=2)
    sharded = SH.shard_params(m, params)
    f = jax.jit(lambda p, x, t, c: U.apply_unet(p, x, t, c, cfg))
    got = np.asarray(f(sharded, x, t, ctx))
    np.testing.assert_allclose(got, want, rtol=5e-4, atol=5e-5)


@pytest.mark.slow  # ~1 min of optimizer steps on the simulated 8-dev mesh
def test_sharded_trainer_loss_decreases(rng):
    """Full dp x tp x sp train step on the virtual mesh: loss is finite and
    params actually update."""
    from ai_rtc_agent_tpu.models import unet as U
    from ai_rtc_agent_tpu.ops import schedule as S
    from ai_rtc_agent_tpu.parallel.trainer import ShardedTrainer, TrainerConfig

    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(1), cfg)
    m = M.make_mesh(dp=2, tp=2, sp=2)

    def unet_apply(p, x, t, ctx, added):
        return U.apply_unet(p, x, t, ctx, cfg, added_cond=added)

    tr = ShardedTrainer(
        unet_apply, S.make_schedule(), m, params, TrainerConfig(learning_rate=1e-3)
    )
    batch = {
        "latents": rng.standard_normal((4, 8, 8, 4)).astype(np.float32),
        "context": rng.standard_normal((4, 7, 32)).astype(np.float32),
    }
    l0 = tr.step(batch, jax.random.PRNGKey(0))
    l1 = tr.step(batch, jax.random.PRNGKey(0))  # same batch+key: loss must drop
    assert np.isfinite(l0) and np.isfinite(l1)
    assert l1 < l0
    assert int(np.asarray(tr.state["step"])) == 2


def test_unet_ring_attention_matches_xla(rng):
    """sp>1 must change the attention code path, not just the test file
    (VERDICT r1 item 6): the full tiny UNet forward under an sp mesh with
    attn_impl="ring" must match the single-device dense result."""
    from ai_rtc_agent_tpu.models import unet as U
    from ai_rtc_agent_tpu.models.layers import sp_attention_mesh

    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(0), cfg)
    x = rng.standard_normal((2, 8, 8, 4)).astype(np.float32)
    t = np.array([5, 9], np.int32)
    ctx = rng.standard_normal((2, 7, 32)).astype(np.float32)

    ref = U.apply_unet(params, x, t, ctx, cfg, attn_impl="xla")

    mesh = M.make_mesh(sp=8)
    with sp_attention_mesh(mesh, axis="sp"):
        out_ring = jax.jit(
            lambda p, x, t, c: U.apply_unet(p, x, t, c, cfg, attn_impl="ring")
        )(params, x, t, ctx)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(ref), atol=2e-4)

    # ulysses needs heads % sp == 0 (tiny has 2 heads -> sp=2 mesh)
    mesh2 = M.make_mesh(sp=2)
    with sp_attention_mesh(mesh2, axis="sp"):
        out_uly = jax.jit(
            lambda p, x, t, c: U.apply_unet(p, x, t, c, cfg, attn_impl="ulysses")
        )(params, x, t, ctx)
    np.testing.assert_allclose(np.asarray(out_uly), np.asarray(ref), atol=2e-4)


def test_unet_ring_attention_no_mesh_falls_back(rng):
    """attn_impl="ring" without an active sp mesh = plain dense attention."""
    from ai_rtc_agent_tpu.models import unet as U

    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(0), cfg)
    x = rng.standard_normal((1, 8, 8, 4)).astype(np.float32)
    t = np.array([3], np.int32)
    ctx = rng.standard_normal((1, 7, 32)).astype(np.float32)
    a = U.apply_unet(params, x, t, ctx, cfg, attn_impl="ring")
    b = U.apply_unet(params, x, t, ctx, cfg, attn_impl="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.slow  # ~1.5 min: two trainer builds + checkpoint IO on 1 core
def test_trainer_checkpoint_roundtrip(rng, tmp_path):
    """Save mid-training, keep stepping, restore -> identical continuation
    (bitwise state; SURVEY sec.5 'checkpoint/resume' for the training tier)."""
    from ai_rtc_agent_tpu.models import unet as U
    from ai_rtc_agent_tpu.ops import schedule as S
    from ai_rtc_agent_tpu.parallel.trainer import ShardedTrainer, TrainerConfig

    cfg = U.UNetConfig.tiny()
    params = U.init_unet(jax.random.PRNGKey(1), cfg)
    m = M.make_mesh(dp=2, tp=2, sp=2)

    def unet_apply(p, x, t, ctx, added):
        return U.apply_unet(p, x, t, ctx, cfg, added_cond=added)

    tr = ShardedTrainer(
        unet_apply, S.make_schedule(), m, params, TrainerConfig(learning_rate=1e-3)
    )
    batch = {
        "latents": rng.standard_normal((4, 8, 8, 4)).astype(np.float32),
        "context": rng.standard_normal((4, 7, 32)).astype(np.float32),
    }
    tr.step(batch, jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpts")
    tr.save(ckpt)

    # fresh trainer restores BITWISE-identical state (the checkpoint
    # guarantee that is actually deterministic)
    tr2 = ShardedTrainer(
        unet_apply, S.make_schedule(), m, params, TrainerConfig(learning_rate=1e-3)
    )
    assert tr2.restore(ckpt)
    assert int(np.asarray(tr2.state["step"])) == 1
    for a, b in zip(jax.tree.leaves(tr.state), jax.tree.leaves(tr2.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    l_continue = tr.step(batch, jax.random.PRNGKey(7))
    l_resumed = tr2.step(batch, jax.random.PRNGKey(7))
    # the continuation itself is NOT guaranteed bitwise: orbax-restored
    # arrays can carry different device layouts than step-produced ones,
    # so XLA may compile a second executable whose reduction order drifts
    # at float32 ulp scale (observed 6e-8 after an unrelated conv-padding
    # change re-fused the graph).  Identical state + tight tolerance is
    # the honest contract.
    np.testing.assert_allclose(
        float(l_resumed), float(l_continue), rtol=0, atol=5e-6
    )
    # restored leaves keep the mesh placement
    some_leaf = jax.tree.leaves(tr2.state["params"])[0]
    assert some_leaf.sharding.mesh.shape == m.shape

    # empty dir -> False
    assert not tr2.restore(str(tmp_path / "nope"))


def test_ring_attention_long_context(rng):
    """Long-context tier (SURVEY sec.5): ring + ulysses at the 8k-token
    scale of SDXL-like latents (SD@512 self-attn is 4096 tokens; SDXL@1024
    is 16k), sharded over the full 8-device mesh — exactness holds at
    scale, memory per device stays O(L/n)."""
    m = M.make_mesh(sp=8)
    B, L, H, D = 1, 8192, 1, 64
    q = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
    want = np.asarray(RA.dense_reference(q, k, v))
    got = np.asarray(RA.ring_attention(q, k, v, m))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
