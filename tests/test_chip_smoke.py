"""chip_smoke.py rehearsed on the CPU, and the rules it leans on: where the
compile cache goes, which native binary is loaded, what hides no device.

The smoke is a script that owns its process (jax platform, trace sink,
recording wrappers on the ops modules), so it runs as a child pinned to
JAX_PLATFORMS=cpu; the child loads no TPU library and needs no chip.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra=None, drop=(), timeout=900):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=timeout,
    )


def test_chip_smoke_rehearsal_runs_every_phase():
    p = _run([SMOKE, "--rehearse", "--seed", "3"])
    assert p.returncode == 0, f"stdout={p.stdout[-3000:]}\nstderr={p.stderr[-3000:]}"
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)
    for phase in ("mega-commit", "device-terms", "catch-up", "node"):
        assert f"   phase {phase}: ok in " in p.stdout
    # the one place the rehearsal drives the device path (XLA value-form)
    assert "-> ladder x" in p.stdout
    assert "refused at height" in p.stdout


def test_chip_smoke_terms_runs_the_device_terms_phase_alone():
    """--terms is the re-measurement of the dispatch's device terms: that
    phase and no other, both sizes, the model's terms beside the fit, and
    no engine but the ladder on a host without a mesh."""
    from cometbft_tpu.crypto import ed25519 as E

    p = _run([SMOKE, "--rehearse", "--terms", "--seed", "5"])
    assert p.returncode == 0, f"stdout={p.stdout[-3000:]}\nstderr={p.stderr[-3000:]}"
    assert json.loads(p.stdout.strip().splitlines()[-1])["ok"] is True
    assert p.stdout.count("== phase ") == 1
    assert "   phase device-terms: ok in " in p.stdout
    assert "n=24 bucket=64: the model says ladder" in p.stdout
    assert "n=48 bucket=64: the model says ladder" in p.stdout
    assert "ladder, NOT A DEVICE NUMBER (rehearsal): fixed " in p.stdout
    assert (f"assumed {E._DEV_LADDER_FIXED_MS:.2f} ms + n x "
            f"{E._DEV_LADDER_US:.3f} us") in p.stdout


@pytest.mark.slow  # XLA:CPU compiles the ladder once a virtual device:
# 11 min cold on this box (6 min for the --terms variant)
def test_chip_smoke_mesh_rehearsal_keeps_its_columns_and_lowers_both():
    """--chips 4 on four virtual devices: the four launches of the mesh
    phase read miss, hit, miss, hit (a column's decompressed pair stays
    on the shards) and both sharded programs are lowered to look for
    their kernels."""
    p = _run([SMOKE, "--rehearse", "--chips", "4", "--seed", "7"],
             {"COMETBFT_TPU_MESH": "on",
              "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
             timeout=2400)
    assert p.returncode == 0, f"stdout={p.stdout[-3000:]}\nstderr={p.stderr[-3000:]}"
    assert "crypto.mesh_submit a_cache: ['miss', 'hit', 'miss', 'hit']" in p.stdout
    assert "sharded decompress_pubkeys[64/4 = 16 a shard]: " in p.stdout
    assert "sharded_verify_rsk[64/4 = 16 a shard]: " in p.stdout
    assert "   phase mesh-4: ok in " in p.stdout


def test_terms_print_the_mesh_beside_the_ladders_line_over_d(
        monkeypatch, capsys):
    """--terms --chips 4, without its compiles: with a mesh up the phase
    fits the mesh's two readings and prints beside the fit what the
    model assumes, the ladder's own line with its per-lane part over the
    device count plus the collective, and what the staging program
    costs a miss. The engines' timings are stand-ins that follow the
    model, so fit == assumed to the digit."""
    import jax

    import chip_smoke as S
    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.parallel.mesh import MeshVerifyEngine

    cpus = jax.devices("cpu")
    if len(cpus) < 4:
        pytest.skip("needs 4 virtual devices")
    eng = MeshVerifyEngine(cpus[:4])
    monkeypatch.setattr(E, "_mesh_engine", lambda: eng)
    line = {"ladder": lambda n: E.dispatch_model(n, 64)["ladder"]["device"],
            "mesh": lambda n: E.dispatch_model(n, 64)["mesh"]["device"]}
    monkeypatch.setattr(
        S, "_engine_timings",
        lambda probe, lanes, engine: {
            "submit_ms": 1.0, "submit_to_verdict_ms": 2.0,
            "device_ms": line[engine](len(lanes)) * 1e3})
    staged = []
    monkeypatch.setattr(
        S, "_median_call_s",
        lambda fn, a, kw: staged.append(fn) or 0.0022)

    class _Probe:
        on_chip = False

        def latest(self, name, lanes):
            return (name, (), {})

    seed = bytes([7]) * 32
    pub = E.Ed25519PubKey(ref.pubkey_from_seed(seed))
    lanes = [(pub, b"terms-%d" % i, ref.sign(seed, b"terms-%d" % i))
             for i in range(48)]
    S.phase_device_terms(_Probe(), lanes, (24, 48), seed=0)
    out = capsys.readouterr().out
    collective_ms = eng.dispatch_terms()["collective_s"] * 1e3
    assumed = (f"assumed {E._DEV_LADDER_FIXED_MS + collective_ms:.2f} ms + "
               f"n x {E._DEV_LADDER_US / 4:.3f} us")
    assert "mesh, NOT A DEVICE NUMBER (rehearsal): fixed " in out
    fit = [ln for ln in out.splitlines() if ln.startswith("   mesh, ")][0]
    assert fit.endswith(assumed), fit
    assert (f"fixed {E._DEV_LADDER_FIXED_MS + collective_ms:.2f} ms + n x "
            f"{E._DEV_LADDER_US / 4:.3f} us through") in fit
    assert ("mesh   NOT A DEVICE NUMBER (rehearsal): "
            f"{line['mesh'](24) * 1e3:.3f} ms in the profile") in out
    assert ("an A-cache miss's sharded decompress_pubkeys 2.200 ms as a "
            "blocked call") in out
    assert eng._stage in staged  # the mesh's miss is its staging program


def test_chip_smoke_refuses_to_start_without_a_chip():
    """No accelerator and no --rehearse: non-zero before any work, and no
    result line on stdout."""
    p = _run([SMOKE], timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


_PRINT_CACHE = ("import jax, cometbft_tpu.ops as o; "
                "print(jax.config.jax_compilation_cache_dir); "
                "print(o.CACHE_DIR)")


def test_compile_cache_dir_follows_the_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the program sets no directory in
    code, jax's own reading of the variable stands."""
    want = str(tmp_path / "placed")
    p = _run(["-c", _PRINT_CACHE], {"JAX_COMPILATION_CACHE_DIR": want},
             timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split()[0] == want


def test_compile_cache_dir_defaults_inside_the_checkout():
    """Unset: one fixed path in the checkout, never under home or tmp."""
    p = _run(["-c", _PRINT_CACHE],
             drop=("JAX_COMPILATION_CACHE_DIR", "XDG_CACHE_HOME"),
             timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    got, fixed = p.stdout.split()
    assert got == fixed == os.path.join(REPO, ".jax_cache")


def test_native_binary_is_keyed_by_sources_flags_and_cpu(monkeypatch):
    """A binary built elsewhere (other CPU features, other flags, other
    sources) has another name and is never the one loaded."""
    from cometbft_tpu.crypto import native

    here = native._so_path()
    assert native.available()
    st = native.build_state()
    assert st["path"] == here and os.path.exists(here)
    assert st["built"] in (True, False) and st["error"] is None
    # the pre-keying name is not something get_lib would ever open
    assert os.path.basename(here) != "_ed25519_native.so"
    monkeypatch.setattr(native, "_cpu_features", lambda: "another cpu")
    other_cpu = native._so_path()
    monkeypatch.undo()
    monkeypatch.setattr(native, "_FLAGS", native._FLAGS + ("-O0",))
    other_flags = native._so_path()
    assert len({here, other_cpu, other_flags}) == 3


def test_a_backend_that_fails_to_start_is_an_error(monkeypatch):
    """_accel_backed no longer turns an exception into 'no accelerator'
    (after which every batch would quietly run on the host), and an
    accelerator of a kind the constants do not describe raises."""
    import jax

    from cometbft_tpu.crypto import ed25519 as E

    monkeypatch.setattr(E, "_ACCEL_BACKED", None)

    def boom():
        raise RuntimeError("TPU runtime failed to initialise")

    monkeypatch.setattr(jax, "default_backend", boom)
    with pytest.raises(RuntimeError, match="failed to initialise"):
        E._accel_backed()

    class Dev:
        device_kind = "TPU v9000"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(RuntimeError, match="TPU v9000"):
        E._accel_backed()
    Dev.device_kind = E.DEVICE_KIND
    assert E._accel_backed() is True


def test_a_mesh_that_cannot_be_built_is_an_error(monkeypatch):
    """get_engine / _mesh_engine let construction errors out instead of
    returning None (a mesh silently becoming one chip)."""
    from cometbft_tpu.crypto import ed25519 as E
    from cometbft_tpu.parallel import mesh as M

    def boom(*a, **kw):
        raise RuntimeError("mesh construction failed")

    monkeypatch.setenv("COMETBFT_TPU_MESH", "on")
    monkeypatch.setattr(M, "MeshVerifyEngine", boom)
    M.reset_engine()
    try:
        with pytest.raises(RuntimeError, match="construction failed"):
            M.get_engine(accel_backed=False)
        M.reset_engine()
        monkeypatch.setattr(E, "_ACCEL_BACKED", False)
        with pytest.raises(RuntimeError, match="construction failed"):
            E._mesh_engine()
        M.reset_engine()
        monkeypatch.setenv("COMETBFT_TPU_MESH", "sideways")
        with pytest.raises(ValueError):
            M.get_engine(accel_backed=False)
    finally:
        M.reset_engine()
