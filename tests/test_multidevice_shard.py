"""Multi-device sharding regression (engine._maybe_shard + _shard_pad).

Before the fix, any vmap-mode batch whose lane count was not an exact
multiple of the visible device count silently fell back to ONE device —
a 5-lane sweep on 4 devices ran on a single core with no warning. Now the
batch is padded to a device multiple with inert sentinel lanes (dropped on
the way out) so awkward grid sizes still shard.

The forced-device-count test must set ``XLA_FLAGS`` before jax
initializes, so it runs in a subprocess; the padding plumbing itself is
also covered in-process.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shard_pad_and_sentinel_lanes_inert():
    import jax

    from repro.core.engine import _PAD_T, _sentinel_trace, _shard_pad, stack_traces
    from repro.traces import BENCHMARKS

    if len(jax.devices()) == 1:
        assert _shard_pad(3) == 0  # nothing to pad toward on one device
    sent = _sentinel_trace(16)
    assert int(np.asarray(sent.t).min()) == _PAD_T  # never due
    tr = BENCHMARKS["trace_example"](n=20, gap=4)
    stacked, ns = stack_traces([tr, tr], pad_lanes=2)
    assert stacked.t.shape[0] == 4
    assert ns == [40, 40]  # real counts only; padding lanes excluded
    assert int(np.asarray(stacked.t)[2:].min()) == _PAD_T


def test_nondivisible_batch_shards_across_forced_devices():
    """3 lanes on a forced 2-device host: one sentinel pad lane, the batch
    axis actually sharded, every real lane bit-identical to its seed run."""
    script = textwrap.dedent("""
        import jax
        import numpy as np
        assert len(jax.devices()) == 2, jax.devices()
        from repro.core import MemSimConfig, simulate, simulate_batch
        from repro.traces import BENCHMARKS

        tr = BENCHMARKS["trace_example"](n=40, gap=5)
        cfg = MemSimConfig(queue_size=32, mem_words=1 << 12)
        timings = {}
        batch = simulate_batch(cfg, tr, num_cycles=2000,
                               queue_sizes=[4, 8, 16], batch_mode="vmap",
                               timings=timings)
        assert timings["pad_lanes"] == 1, timings
        assert timings["sharded"] is True, timings
        assert timings["devices"] == 2, timings
        for q, res in zip([4, 8, 16], batch):
            ref = simulate(MemSimConfig(queue_size=q, mem_words=1 << 12),
                           tr, num_cycles=2000)
            for f in ("t_admit", "t_dispatch", "t_start", "t_complete",
                      "rdata"):
                np.testing.assert_array_equal(getattr(ref, f),
                                              getattr(res, f), err_msg=f)
            for k in ref.counters:
                np.testing.assert_array_equal(
                    np.asarray(ref.counters[k]),
                    np.asarray(res.counters[k]), err_msg=k)
            assert ref.blocked_arrival == res.blocked_arrival
            assert ref.blocked_dispatch == res.blocked_dispatch
        print("SHARDED-PAD-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(_ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=_ROOT)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}"
    assert "SHARDED-PAD-OK" in proc.stdout


def test_mesh_pjit_integration_with_per_device_throughput():
    """ROADMAP multi-device scale-out: a real pjit/mesh exercise of
    ``distributed/shard`` on a forced 2-device host.

    Inside the subprocess: (1) a Mesh is bound via ``shard.use_mesh`` and a
    jit'd function constrained with ``shard.constrain`` must come out
    actually spanning both devices; (2) a vmap-mode ``simulate_batch``
    sharded over the mesh stays bit-exact per lane; (3) a lanes-mode batch
    records per-lane device/steps/run_s timings, both devices must have
    served lanes, and the derived per-device throughput — the numbers
    ``benchmarks/run.py`` publishes in the BENCH JSON ``engine.mesh``
    section — must be positive."""
    script = textwrap.dedent("""
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 2, jax.devices()
        from jax.sharding import Mesh
        from repro.core import MemSimConfig, simulate, simulate_batch
        from repro.distributed import shard as shard_lib
        from repro.traces import BENCHMARKS

        # (1) constrain() under an active mesh must span both devices
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        with shard_lib.use_mesh(mesh):
            sharding = shard_lib.named(mesh, "data")

            @jax.jit
            def probe(x):
                return shard_lib.constrain(x * 2 + 1, "data", None)

            x = jax.device_put(jnp.zeros((4, 8), jnp.int32), sharding)
            y = probe(x)
            assert len(y.sharding.device_set) == 2, y.sharding
            np.testing.assert_array_equal(np.asarray(y), np.ones((4, 8)))

        # (2) mesh-sharded vmap batch stays bit-exact per lane
        tr = BENCHMARKS["trace_example"](n=40, gap=5)
        cfg = MemSimConfig(queue_size=32, mem_words=1 << 12)
        timings = {}
        batch = simulate_batch(cfg, tr, num_cycles=2000,
                               queue_sizes=[4, 8, 16, 32],
                               batch_mode="vmap", timings=timings)
        assert timings["sharded"] is True, timings
        for q, res in zip([4, 8, 16, 32], batch):
            ref = simulate(MemSimConfig(queue_size=q, mem_words=1 << 12),
                           tr, num_cycles=2000)
            np.testing.assert_array_equal(ref.t_complete, res.t_complete, q)
            np.testing.assert_array_equal(ref.rdata, res.rdata, q)
        # the per-cycle scan batch is split over the mesh the same way
        scan = simulate_batch(cfg, tr, num_cycles=2000,
                              queue_sizes=[4, 8, 16, 32], batch_mode="vmap",
                              cycle_skip=False)
        for q, res, sres in zip([4, 8, 16, 32], batch, scan):
            np.testing.assert_array_equal(res.t_complete, sres.t_complete, q)
            np.testing.assert_array_equal(res.rdata, sres.rdata, q)

        # (3) lanes mode: per-lane device attribution -> per-device
        # throughput; both devices must serve lanes
        timings = {}
        simulate_batch(cfg, tr, num_cycles=2000, queue_sizes=[8] * 4,
                       batch_mode="lanes", timings=timings)
        lanes = timings["per_lane"]
        assert len(lanes) == 4, lanes
        devs = {rec["device"] for rec in lanes}
        assert devs == {0, 1}, lanes
        per_dev = {}
        for rec in lanes:
            d = per_dev.setdefault(rec["device"], [0, 0.0])
            d[0] += rec["steps"]
            d[1] += rec["run_s"]
        for dev, (steps, run_s) in sorted(per_dev.items()):
            tput = steps / max(run_s, 1e-9)
            assert steps > 0 and tput > 0, (dev, steps, run_s)
            print(f"MESH-DEV dev={dev} steps={steps} "
                  f"steps_per_sec={tput:.0f}")
        print("MESH-PJIT-OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (os.path.join(_ROOT, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900,
                          cwd=_ROOT)
    assert proc.returncode == 0, f"stderr:\n{proc.stderr}"
    assert "MESH-PJIT-OK" in proc.stdout
    assert proc.stdout.count("MESH-DEV") == 2
