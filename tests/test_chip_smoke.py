"""CPU rehearsal of ``chip_smoke.py``: its phase functions at reduced width.

The chip run itself needs a TPU; these tests run the same phases on the
CPU (interpret-mode kernels) so every change to the served path is
checked against the smoke check's contract before it reaches a chip.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_one_chip_phases_rehearse_on_cpu(tmp_path, capsys):
    """(b)-(e) at reduced width: paged decode with prefix hits, the logit
    check, bit-identical HOST_RAM and LOCAL_DISK restores with zero builds
    and compiles, and the kernel phase."""
    base = chip_smoke.one_chip(full_config=False, spill_dir=str(tmp_path))
    assert len(base) == chip_smoke.N_CLAIMS
    out = capsys.readouterr().out
    for phase in ("[b]", "[c]", "[d]", "[e]"):
        assert phase in out
    assert "outputs=bit-identical" in out


def test_main_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "platform=cpu" in out


def test_four_chip_phase_rehearses_on_four_cpu_devices(tmp_path):
    """Four live workers on four virtual CPU devices in a child process:
    PEER joiners with zero builds and compiles, outputs bit-identical to
    the donor's, and everything on each worker's own device."""
    code = textwrap.dedent(f"""
        import chip_smoke
        chip_smoke.check_device("cpu", min_count=4)
        chip_smoke.four_chips(full_config=False, spill_dir={str(tmp_path)!r})
        print("REHEARSED")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1] == "REHEARSED"
    line = next(ln for ln in lines if ln.startswith("[4chips]"))
    assert "devices=[0, 1, 2, 3]" in line
    assert "joiner_compiles=0" in line and "bit-identical" in line
    assert not any(ln.startswith("{") and json.loads(ln).get("ok")
                   for ln in lines)


def test_joiners_take_peer_under_chip_calibration(tmp_path):
    """The four-chip joiners' ladder, priced with what one TPU v5e measured:
    a PEER bootstrap of the published-width context (3.42 GB) in 20.48 s
    of export + restore, and donor export turns at 0.12 GB/s. With the
    smoke context's published-width footprint every joiner still comes
    over PEER, and none runs the builder (a context whose only cold path
    is its builder has no FS rung to lose to)."""
    import dataclasses
    from repro.core import ContextMode, PCMClient, PCMManager
    from repro.core.transfer import FetchSource, TransferPlanner
    full = chip_smoke.recipe_for(full_config=True, seed=0)
    recipe = dataclasses.replace(
        chip_smoke.recipe_for(full_config=False, seed=0),
        **{f: getattr(full, f) for f in ("artifact_bytes", "env_bytes",
                                         "host_bytes", "device_bytes")})
    planner = TransferPlanner()
    measured = planner.peer_plan(full.host_bytes, {"chip0"}, now=0.0)
    planner.complete(measured, now=0.0, measured_seconds=20.48)
    planner.observe_stage("d2h", full.host_bytes, 28.1)
    mgr = PCMManager(mode=ContextMode.FULL, n_workers=1, planner=planner,
                     spill_dir=str(tmp_path))
    client = PCMClient(backend=mgr)
    try:
        handle = client.context(recipe)
        handle.warm_up()
        joiners, _ = chip_smoke.add_joiners(client, handle, 2)
        for w in joiners:
            lib = mgr.workers[w].library
            assert lib.fetch_sources == [FetchSource.PEER], lib.fetch_sources
            assert lib.builder_calls == 0
    finally:
        client.shutdown()


@pytest.mark.parametrize("via", ["disk", "process"])
def test_executable_of_one_device_loads_onto_another(tmp_path, via):
    """An engine on device 1 takes the executables an engine on device 0
    compiled — from the serialized on-disk payload, or serialized from the
    in-process one — with zero compiles, and decodes bit-identically."""
    code = textwrap.dedent("""
        import json, sys
        import jax
        from repro.configs import get_reduced_config
        from repro.models import build_model
        from repro.serving import InferenceEngine, engine
        if sys.argv[1] == "disk":
            engine.set_aot_cache_dir(sys.argv[2])
        d0, d1 = jax.devices()[:2]
        model = build_model(get_reduced_config("smollm2-1.7b"))
        params = jax.device_put(model.init(jax.random.PRNGKey(0)), d0)
        eng = InferenceEngine(model, params, slots=2, cache_len=64,
                              prefill_buckets=(32,), paged=True)
        prompts = [[5, 6, 7, 8], [9, 10, 11]]
        want = eng.generate(prompts, max_new_tokens=3)
        if sys.argv[1] == "disk":
            engine._AOT_EXES.clear()        # only the files remain
        clone = eng.clone_offloaded()
        with jax.default_device(d1):
            clone.restore_device_state(eng.export_template())
        got = clone.generate(prompts, max_new_tokens=3)
        print(json.dumps({
            "same": got == want, "compiles": clone.stats.compiles,
            "hits": clone.stats.aot_cache_hits,
            "on_d1": all(leaf.devices() == {d1} for leaf in
                         jax.tree_util.tree_leaves(clone.params))}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", code, via,
                           str(tmp_path)], cwd=str(tmp_path), env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"same": True, "compiles": 0, "hits": got["hits"],
                   "on_d1": True}, got
    assert got["hits"] > 0


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_root(tmp_path, from_env):
    """One fixed root for both caches: JAX_COMPILATION_CACHE_DIR when set
    (and nothing written elsewhere), else ``.compile_cache`` in the
    checkout; a second process compiling the same program hits it. Run in
    children so the JAX config of this process is kept; the checkout's own
    directory is stood in for by ``fallback`` to keep the checkout clean."""
    code = textwrap.dedent("""
        import json, sys
        import jax, jax.numpy as jnp
        from jax._src import monitoring
        from repro.launch import compile_cache
        from repro.serving import engine
        default = compile_cache.CHECKOUT_CACHE
        compile_cache.CHECKOUT_CACHE = sys.argv[1]
        hits = []
        monitoring.register_event_listener(
            lambda event, **kw: hits.append(event)
            if event == "/jax/compilation_cache/cache_hits" else None)
        root = compile_cache.configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1).lower(jnp.ones(3)).compile()
        print(json.dumps({"root": root, "default": default,
                          "jax": jax.config.jax_compilation_cache_dir,
                          "aot": engine._AOT_CACHE_DIR, "hits": len(hits)}))
    """)
    env_dir, fallback = tmp_path / "env", tmp_path / "fallback"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    runs = []
    for _ in range(2):
        proc = subprocess.run([sys.executable, "-c", code, str(fallback)],
                              cwd=str(tmp_path), env=env,
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-3000:]
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    first, second = runs
    want = str(env_dir if from_env else fallback)
    assert first["root"] == first["jax"] == want
    assert first["aot"] == os.path.join(want, "pcm-aot")
    assert first["default"] == os.path.join(REPO, ".compile_cache")
    assert first["hits"] == 0 and second["hits"] > 0, runs
    assert sorted(p.name for p in tmp_path.iterdir()) == \
        [os.path.basename(want)]
