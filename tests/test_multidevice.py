"""Multi-device tests run in subprocesses so they can set
--xla_force_host_platform_device_count without polluting this process
(conftest deliberately leaves the flag unset)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 4) -> str:
    prog = f"import os\n" \
        f"os.environ['XLA_FLAGS'] = " \
        f"'--xla_force_host_platform_device_count={devices}'\n" \
        + textwrap.dedent(code)
    # JAX_PLATFORMS must survive the env strip: without it jax probes
    # non-CPU platform plugins on first backend init, which blocks for
    # ~8 minutes per subprocess in offline containers.
    r = subprocess.run([sys.executable, "-c", prog],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": str(REPO / "src"),
                            "PATH": "/usr/bin:/bin",
                            "HOME": "/root",
                            "JAX_PLATFORMS":
                                os.environ.get("JAX_PLATFORMS", "cpu")},
                       timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_disaggregated_execution_across_real_devices():
    """Stages placed on distinct host devices must still reproduce the
    reference output — exercising real cross-device transfers."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import analyzer, planner, marker
    from repro.core.costmodel import GPU_A100, GPU_L40S
    from repro.core.executor import StagedExecutable

    def model(x, params):
        for i, (w1, w2) in enumerate(params):
            x = marker.wrap(lambda y, a=w1, b=w2: jax.nn.gelu(y @ a) @ b,
                            layer=i)(x)
        return jnp.tanh(x)

    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 9)
    params = [(jax.random.normal(ks[2*i], (32, 64)) * 0.1,
               jax.random.normal(ks[2*i+1], (64, 32)) * 0.1)
              for i in range(4)]
    x = jax.random.normal(ks[8], (4, 32))
    traced = analyzer.analyze(model, x, params)
    plan = planner.plan(traced.graph, [GPU_A100, GPU_L40S],
                        policy="throughput", cache=False)
    devs = jax.devices()
    assert len(devs) == 4, devs
    exe = StagedExecutable(traced, plan, [devs[0], devs[1]])
    got = exe(x, params)
    want = jax.jit(model)(x, params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    # outputs of stages must actually live on their assigned devices
    used = {cs.device for cs in exe.stages}
    assert len(used) == 2, used
    print("MULTIDEVICE_OK", len(exe.stages))
    """)
    assert "MULTIDEVICE_OK" in out


def test_pipelined_runner_across_devices():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import analyzer, planner
    from repro.core.costmodel import GPU_A100, GPU_L40S
    from repro.core.executor import StagedExecutable
    from repro.core.pipeline import PipelinedRunner

    def fn(x, w):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x
    x = jnp.ones((8, 16)); w = jnp.eye(16) * 0.7
    traced = analyzer.analyze(fn, x, w)
    plan = planner.plan(traced.graph, [GPU_A100, GPU_L40S], cache=False)
    devs = jax.devices()
    exe = StagedExecutable(traced, plan, [devs[0], devs[1]])
    runner = PipelinedRunner(exe, max_inflight=3)
    outs, stats = runner.run([((x + i, w), {}) for i in range(5)])
    for i, o in enumerate(outs):
        np.testing.assert_allclose(np.asarray(o),
                                   np.asarray(jax.jit(fn)(x + i, w)),
                                   rtol=1e-5)
    print("PIPELINE_OK", stats.completed)
    """)
    assert "PIPELINE_OK 5" in out


def test_straggler_override_preserves_slot_devices():
    """Re-running a unit on a fallback device must leave its exports
    committed to the unit's HOME device: later same-device units read
    those slots directly, and jit rejects mixed-device inputs."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import analyzer, planner
    from repro.core.costmodel import GPU_A100, GPU_L40S
    from repro.core.executor import StagedExecutable

    def fn(x, w):
        for _ in range(6):
            x = jnp.tanh(x @ w)
        return x
    x = jnp.ones((8, 16)); w = jnp.eye(16) * 0.7
    traced = analyzer.analyze(fn, x, w)
    plan = planner.plan(traced.graph, [GPU_A100, GPU_L40S], cache=False)
    devs = jax.devices()
    exe = StagedExecutable(traced, plan, [devs[0], devs[1]])
    assert exe.num_units > 1, exe.num_units
    slots = exe.init_slots(x, w)
    for i in range(exe.num_units):
        # every unit is a straggler: rerun all on a third device
        exe.run_unit(slots, i, device_override=devs[3])
        for v, fs in zip(exe.unit_outputs(slots, i),
                         [exe.program.fused[i]] * 99):
            assert v.devices() == {fs.device}, (i, v.devices())
    got = exe.collect_slots(slots)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.jit(fn)(x, w)), rtol=1e-5)
    print("OVERRIDE_PLACEMENT_OK")
    """)
    assert "OVERRIDE_PLACEMENT_OK" in out


def test_pjit_mesh_train_step_runs():
    """A sharded train step must actually execute on an 8-device host
    mesh (not just compile) — validates the sharding rules end to end."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np, dataclasses
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.configs as C
    from repro.models import model as M
    from repro.train import optim
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(C.get_smoke("llama3_8b"), dtype="float32",
                              num_heads=4, num_kv_heads=4, d_ff=128)
    ocfg = optim.AdamWConfig(warmup_steps=1, total_steps=4)
    params = M.init_params(cfg)
    opt = optim.init(ocfg, params)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "targets": jnp.zeros((4, 16), jnp.int32)}
    p_sh = SH.param_shardings(params, mesh, SH.TRAIN_RULES)
    rep = NamedSharding(mesh, P())
    o_sh = optim.AdamWState(step=rep, mu=p_sh, nu=p_sh, master=p_sh)
    b_sh = {k: NamedSharding(mesh, P("data")) for k in batch}

    def train_step(params, opt_state, batch):
        def lf(p):
            return M.loss_fn(p, cfg, batch["tokens"], batch["targets"])
        loss, grads = jax.value_and_grad(lf)(params)
        p2, o2 = optim.apply(ocfg, grads, opt_state, params)
        return p2, o2, loss

    with mesh:
        step = jax.jit(train_step, in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, rep))
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)
        batch = jax.device_put(batch, b_sh)
        l0 = None
        for i in range(3):
            params, opt, loss = step(params, opt, batch)
            l0 = l0 if l0 is not None else float(loss)
        assert float(loss) < l0, (float(loss), l0)
    print("PJIT_TRAIN_OK", float(loss))
    """, devices=8)
    assert "PJIT_TRAIN_OK" in out


def test_gradient_compression_crosspod_allreduce():
    """EF-int8 compressed gradient all-reduce via shard_map over a pod
    axis: the mean of decompressed shards must track the true mean."""
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.train.compress import quantize_int8, dequantize_int8

    mesh = make_mesh((4,), ("pod",))

    def compressed_allreduce(g):
        q, s = quantize_int8(g)
        y = dequantize_int8(q, s)       # wire format
        return jax.lax.pmean(y, "pod")

    f = jax.shard_map(compressed_allreduce, mesh=mesh,
                      in_specs=P("pod"), out_specs=P("pod"))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    out = f(g)
    true_mean = jnp.tile(g.reshape(4, 2, 32).mean(0), (4, 1))
    err = float(jnp.abs(out - true_mean).max())
    assert err < 0.05, err
    print("COMPRESS_ALLREDUCE_OK", err)
    """)
    assert "COMPRESS_ALLREDUCE_OK" in out
