"""``chip_smoke.py`` rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU (and these tests hold it
to that); its phase functions take their sizes as arguments, so the
same code — the same entry points, checks and JSON facts — runs here on
a 2-layer, 64-wide LM and a CIFAR ResNet-20. What this cannot show is
anything about the chip: that is what the script is for.
"""
import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_LM = dict(vocab=300, hidden=64, layers=2, heads=4, ffn=128,
               positions=64, seq=32, batch=4, steps=8, steps_per_sync=4,
               lr=3e-3, data_vocab=32)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(_ROOT, "chip_smoke.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def compiles(smoke):
    return smoke._Compiles()


def _is_fact_line(facts, phase):
    line = json.loads(json.dumps(facts))      # one JSON object
    assert line["phase"] == phase
    for key in ("widths", "seconds", "compiles", "kernels",
                "peak_bytes_in_use"):
        assert key in line, key
    assert set(line["seconds"]) == {"setup_build_and_compile", "steps"}
    assert set(line["kernels"]) == {"taken", "declined"}
    # facts of a smoke run, never a rate or a utilisation
    assert not [k for k in line if "per_sec" in k or "mfu" in k.lower()
                or "util" in k.lower()]
    return line


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_to_run_without_a_tpu(smoke, argv, capsys):
    """JAX is held to the CPU here: non-zero exit, no result line."""
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "not a TPU" in out.err


def test_full_width_sizes_are_the_published_ones(smoke):
    """What the driver's run uses: ResNet-50 ImageNet at batch >= 64,
    the GPT-2-small widths, 16 slots x 1024 at the default ladder."""
    assert (smoke.RESNET["depth"], smoke.RESNET["classes"],
            smoke.RESNET["image"]) == (50, 1000, 224)
    assert smoke.RESNET["batch"] >= 64
    lm = smoke.LM
    assert (lm["layers"], lm["hidden"], lm["heads"], lm["ffn"],
            lm["vocab"], lm["positions"], lm["seq"]) == (
                12, 768, 12, 3072, 50257, 1024, 1024)
    assert lm["steps_per_sync"] > 1
    assert (smoke.SERVE["slots"], smoke.SERVE["max_len"],
            smoke.SERVE["length_buckets"]) == (16, 1024, None)
    assert smoke.MESH["chips"] == 4


def test_train_resnet_phase(smoke, compiles):
    from bigdl_tpu.utils.engine import Engine

    cfg = dict(smoke.RESNET, depth=20, classes=10, dataset="CIFAR10",
               image=32, batch=8, steps=4)
    line = _is_fact_line(smoke.train_resnet50(cfg, 0, compiles),
                         "train_resnet50")
    assert line["loss_last"] < line["loss_first"]
    # the phase's bf16 compute dtype does not leak into the next one
    assert Engine.compute_dtype() == "float32"


def test_train_lm_phase(smoke, compiles):
    line = _is_fact_line(smoke.train_lm(TINY_LM, 0, compiles), "train_lm")
    assert line["steps"] == 8 and line["loss_last"] < line["loss_first"]
    assert line["widths"]["steps_per_sync"] == 4


def test_serve_lm_phase(smoke, compiles):
    """The serve phase with the decode kernel switched on, as the TPU
    default has it (here it runs in the pallas interpreter, which the
    phase reports and, on a TPU, refuses)."""
    from bigdl_tpu import kernels
    from bigdl_tpu.kernels import KernelConfig

    cfg = dict(smoke.SERVE, slots=4, max_len=64, prompt_lens=(3, 9, 20),
               new_tokens=6)
    with kernels.use(KernelConfig(decode_attention=True)):
        line = _is_fact_line(
            smoke.serve_lm(TINY_LM, cfg, 0, compiles), "serve_lm")
    assert line["tokens_produced"] == 18
    assert line["engine_programs"] <= line["engine_program_bound"]
    assert line["decode_kernel_taken"] > 0
    assert line["interpret_mode"] is True
    ref = line["reference"]
    assert ref["decisive_positions_equal"] > 0


def test_serve_lm_phase_fails_on_a_wrong_token(smoke, compiles,
                                              monkeypatch):
    """The comparison bites: a service that streams a token the
    re-forward does not rank first fails the phase."""
    import numpy as np

    from bigdl_tpu.generation.stream import TokenStream

    real = TokenStream.result

    def off_by_one(self, timeout=None):
        out = np.array(real(self, timeout))
        out[-1] = (out[-1] + 7) % TINY_LM["vocab"]
        return out

    monkeypatch.setattr(TokenStream, "result", off_by_one)
    cfg = dict(smoke.SERVE, slots=4, max_len=64, prompt_lens=(3, 9, 20),
               new_tokens=6)
    with pytest.raises(AssertionError, match="serve_lm"):
        smoke.serve_lm(TINY_LM, cfg, 0, compiles)


def test_mesh_phase_on_four_virtual_devices(smoke, compiles):
    """``--chips 4``'s phase on four of conftest's eight virtual CPU
    devices: a rehearsal of the sharding rules, not evidence about
    chips."""
    line = _is_fact_line(
        smoke.train_lm_mesh(TINY_LM, dict(smoke.MESH), 0, compiles),
        "train_lm_mesh")
    assert line["devices_holding_shards"] == 4
    assert line["loss_max_abs_diff"] <= line["loss_tolerance"]
    opt = line["opt_state_bytes"]
    assert opt["on_one_device"] * 4 <= opt["whole"] * 1.05
    assert line["batch_bytes"]["on_one_device"] * 4 \
        == line["batch_bytes"]["whole"]


# ------------------------------------- one process per chip, one cache

def test_importing_the_package_initialises_no_backend():
    """A process that has merely imported the package must not hold
    the chip: importing opens no backend (checked in a fresh
    interpreter — this one has long since opened the CPU's)."""
    import subprocess
    import sys

    code = (
        "import bigdl_tpu, bigdl_tpu.generation, bigdl_tpu.fleet, "
        "bigdl_tpu.kernels, bigdl_tpu.optim.optimizer, "
        "bigdl_tpu.models, bigdl_tpu.tools.launch, "
        "bigdl_tpu.utils.engine\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'importing bigdl_tpu opened a backend'\n"
        "import jax\n"
        "assert jax.config.jax_compilation_cache_dir is None, "
        "'importing bigdl_tpu placed a compile cache'\n")
    env = dict(os.environ, PYTHONPATH=_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=_ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]


def test_compile_cache_helper_is_placed_from_outside_or_fixed(
        monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: the helper touches nothing
    (jax reads the variable). Unset: the same path inside the checkout
    on every call — nothing made from a temp dir, a pid or the clock."""
    import jax

    from bigdl_tpu.utils import engine

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert engine.enable_compile_cache() == "/placed/outside"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first = engine.enable_compile_cache()
        assert first == os.path.join(_ROOT, ".jax_cache")
        assert engine.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        # the tests themselves compile fresh: put the setting back
        jax.config.update("jax_compilation_cache_dir", before)
