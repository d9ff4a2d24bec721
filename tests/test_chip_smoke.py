"""``chip_smoke.py`` at CPU size: every phase function on a tiny model
(ResNet-18 at 32x32, a 2-layer LM), the four-chip option's two checks on
four virtual devices, and the script's own verdict without a chip.  The
sizes the chip runs, and the chip, are the builder's and the driver's."""

import glob
import json
import os
import subprocess
import sys

import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY_RESNET = {"num_layers": 18, "image_shape": (3, 32, 32),
               "num_classes": 10}
TINY_LM = (256, 64, 4, 2, 128, 96)


@pytest.fixture(autouse=True)
def _telemetry():
    """The phases read their compile and kernel-path accounting from the
    registry, as ``chip_smoke.run`` arranges."""
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.disable()
    telemetry.reset()


def _short_epoch(args, kv):
    """``data.get_rec_iter``'s ``--benchmark 1`` iterator, 6 steps of it."""
    from common import data as ex_data

    shape = tuple(int(x) for x in args.image_shape.split(","))
    return ex_data.SyntheticDataIter(
        args.num_classes, (args.batch_size,) + shape, 6), None


def test_phase_fit():
    out = chip_smoke.phase_fit(mx.cpu(), batch=8, data_loader=_short_epoch,
                               **TINY_RESNET)
    assert out["phase"] == "fit" and out["steps"] == 6
    assert out["arrays_on"] == "cpu" and out["dtypes"] == ["float32"]
    assert out["loss_last"] < out["loss_first"]
    assert out["programs_built"] >= 1


def test_phase_bulk(monkeypatch):
    # the phase turns the fused step on for its process; keep that from
    # leaking into whatever this worker runs next
    monkeypatch.setenv("MXNET_FUSE_TRAIN_STEP", "1")
    out = chip_smoke.phase_bulk(mx.cpu(), batch=4, bulk=2, **TINY_RESNET)
    assert out["executor_kinds"] == ["train_sgd_scan"]
    assert out["steps"] == 4 and "bfloat16" in out["dtypes"]


def test_phase_serve(tmp_path):
    out = chip_smoke.phase_serve(mx.cpu(), buckets=(1, 4),
                                 work_dir=str(tmp_path / "published"),
                                 **TINY_RESNET)
    assert not (tmp_path / "published").exists()
    assert out["requests"] == 3 and out["healthz"] == "ok"
    assert out["rel_diff_vs_module_predict"] <= 1e-5
    assert out["largest_logit"] > 0


def test_phase_decode():
    out = chip_smoke.phase_decode(
        jax.devices()[0], lm_shape=TINY_LM, slots=4,
        prefill_buckets=(8, 32), prompt_lens=(3, 7, 20), max_new_tokens=6)
    assert out["decode_step_programs"] == {"dense": 1, "paged": 1}
    assert out["dense_equals_paged"]
    judged = out["greedy_vs_forward_logits"]
    # on the CPU both programs multiply in float32: bit for bit
    assert judged["argmax_matches"] == judged["positions"] == 18
    # off the chip the flash kernel is refused, and the refusal is counted;
    # a cache of 96 positions is one rung, and the paged step attends over
    # all it gathered whatever the rungs
    assert out["kernel_paths"] == {
        "op=FlashAttention,path=xla,reason=not_tpu": 2,
        "op=attend_slots,path=whole,reason=one_rung": 1,
        "op=attend_slots,path=whole,reason=gathered": 1}
    assert not any(k["tpu_custom_call"] for k in out["pallas"].values())


def test_four_chip_mesh_fit_on_virtual_devices(monkeypatch):
    # the suite has 8 virtual devices; the four-chip host has 4
    monkeypatch.setenv("MXNET_MESH_DEVICES", "4")
    out = chip_smoke.phase_mesh_fit(mx.cpu(), n_devices=4, batch=8,
                                    steps=5, **TINY_RESNET)
    assert out["devices"] == 4 and out["data_shard_rows"] == 2
    assert out["collectives_over_all_devices"] > 0
    assert out["losses_mesh"] == pytest.approx(out["losses_one_device"],
                                               rel=out["loss_rtol"])


def test_four_chip_replicas_on_virtual_devices():
    out = chip_smoke.phase_replicas(
        n_devices=4, lm_shape=TINY_LM, slots=4, prefill_buckets=(8,),
        prompt_len=5, max_new_tokens=4)
    assert len(set(out["parameter_devices"])) == 4
    assert out["tokens_per_replica"] == [4, 4, 4, 4]
    assert out["replicas_agree"]


def test_collective_group_sizes_reads_both_spellings():
    text = "\n".join([
        "  %ar = f32[8] all-reduce(f32[8] %x), channel_id=1, "
        "replica_groups={{0,1,2,3}}, to_apply=%add",
        "  %rs = f32[2] reduce-scatter(f32[8] %x), channel_id=2, "
        "replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add",
        "  %ag = f32[8] all-gather(f32[2] %y), replica_groups={{0,1}}",
        "  %ars = f32[8] all-reduce-start(f32[8] %x), "
        "replica_groups=[2,2]<=[4], to_apply=%add"])
    assert chip_smoke._collective_group_sizes(text) == [4, 4, 2]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_script_fails_without_a_chip(argv):
    """No accelerator: a non-zero exit, ``"ok": false`` as the last line,
    no result, and the checkout's built artefacts left alone."""
    built = os.path.join(ROOT, "mxnet_tpu", "native")
    before = sorted(os.listdir(built))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")] + argv,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": False}
    assert not any('"ok": true' in ln for ln in lines)
    assert "needs" in proc.stderr and "TPU chip" in proc.stderr
    assert sorted(os.listdir(built)) == before


_NO_BACKEND = r"""
import sys
sys.argv = ["probe"]
import mxnet_tpu, mxnet_tpu.io, mxnet_tpu.models, mxnet_tpu.serving
import chip_smoke
from mxnet_tpu.sentinel import Supervisor

rc = Supervisor([sys.executable, "-c", "print('child ran')"]).run()
print("supervised child rc", rc)
import jax
try:
    jax.devices()
except RuntimeError as e:
    print("a backend init raises:", str(e)[:60])
"""


def test_parents_of_chip_children_initialise_no_backend():
    """One process per chip: whatever starts a child that may need the
    chip must not have taken it.  Under a platform name JAX does not
    know, ANY backend initialisation raises — so importing the package and
    the smoke, and supervising a child (``sentinel.Supervisor``, what
    ``tools/supervise.py`` runs), passing here proves none of them touches
    a backend."""
    proc = subprocess.run(
        [sys.executable, "-c", _NO_BACKEND],
        env=dict(os.environ, JAX_PLATFORMS="no_such_platform",
                 PYTHONPATH=ROOT), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "supervised child rc 0" in proc.stdout
    assert "a backend init raises" in proc.stdout


_IMPORT_TOOL = r"""
import importlib.util, sys
path = sys.argv[1]
sys.argv = [path]
spec = importlib.util.spec_from_file_location("tool", path)
spec.loader.exec_module(importlib.util.module_from_spec(spec))
"""


@pytest.mark.parametrize("tool", sorted(
    os.path.basename(p)
    for p in glob.glob(os.path.join(ROOT, "tools", "perf", "*.py"))))
def test_tools_perf_import_without_a_backend(tool):
    """``tools/perf/README.md``'s rule: a hand tool stays while it runs on
    this tree.  Each imports, from the root as a chip call runs it, against
    the code that is there, and touches no backend while it does (a tool
    that starts children for the chip must not hold it)."""
    proc = subprocess.run(
        [sys.executable, "-c", _IMPORT_TOOL,
         os.path.join("tools", "perf", tool)],
        env=dict(os.environ, JAX_PLATFORMS="no_such_platform",
                 PYTHONPATH=ROOT), cwd=ROOT, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
