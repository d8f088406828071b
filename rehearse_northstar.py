"""One on-chip rehearsal of the FULL north-star pipeline (round-5).

BASELINE.json's north star is "ImageNet AlexNet ≥58% top-1, real data,
augmented" — real ImageNet cannot exist in this environment (no network),
but every stage of the pipeline that run would use CAN be exercised as
ONE run on the real chip, which is exactly what this script does:

  1. synthesize a JPEG class-directory tree (PIL),
  2. ``import_image_directory`` → streaming decode into the mmap'd npy
     dataset format (``data/images.py``),
  3. train AlexNet at 224×224 via ``asyncsgd.imagenet`` with
     ``--native true --augment-mode rrc`` (C++ ``mpit_rrc_batch``
     augmentation) + checkpointing + periodic full-val sweeps,
  4. SIGTERM the run mid-flight (preemption drain → checkpoint),
  5. resume from the checkpoint and finish, ending with the padded
     full-val top-1/top-5 sweep,
  6. time a synthetic-stream control at the same shapes to quantify the
     real-data input-pipeline overhead.

Run: ``python rehearse_northstar.py [workdir]`` (defaults to a temp
dir). Prints progress lines and a final ``REHEARSAL {...}`` JSON line;
exits non-zero on any failed stage. Results are recorded in
BENCHMARKS.md §"North-star rehearsal".

Sizing: 16 classes × 48 images stored at 256² (train) + 8 val each —
small enough to synthesize in seconds, big enough that batches, RRC
crops to 224², the val remainder (pad-and-mask), and seek-based resume
all take their production paths.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
CLASSES = 16
PER_CLASS = 48
VAL_PER_CLASS = 8
STORE = 256
TRAIN = 224
BATCH = 64
RESUME_STEPS = 30  # steps to run AFTER the drain point


def make_jpeg_tree(root: str) -> None:
    from PIL import Image

    rng = np.random.RandomState(0)
    hues = rng.randint(0, 255, size=(CLASSES, 3))
    for split, n in (("train", PER_CLASS), ("val", VAL_PER_CLASS)):
        for c in range(CLASSES):
            cdir = os.path.join(root, split, f"class{c:02d}")
            os.makedirs(cdir, exist_ok=True)
            for i in range(n):
                h = int(rng.randint(220, 400))
                w = int(rng.randint(220, 400))
                img = np.clip(
                    np.full((h, w, 3), hues[c], np.float32)
                    + rng.randn(h, w, 3) * 25,
                    0,
                    255,
                ).astype(np.uint8)
                Image.fromarray(img).save(
                    os.path.join(cdir, f"im{i:03d}.jpg"), quality=90
                )


def _last_result(text: str) -> dict:
    """The launcher prints the run's result dict as its last JSON line
    (``mpit_tpu.asyncsgd.__main__``); metric JSONL rows precede it."""
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "steps" in rec:
                return rec
    return {}


def _train_cmd(ds_dir: str, ckpt: str, steps: int) -> list[str]:
    return [
        sys.executable,
        "-m",
        "mpit_tpu.asyncsgd",
        "imagenet",
        "--data-dir", ds_dir,
        "--train-size", str(TRAIN),
        "--steps", str(steps),
        "--batch-size", str(BATCH),
        "--lr", "0.005",
        "--native", "true",
        "--augment", "true",
        "--augment-mode", "rrc",
        "--log-every", "5",
        "--eval-every", "20",
        "--eval-batch", "64",
        "--ckpt-dir", ckpt,
        "--ckpt-every", "10",
    ]


def main(workdir: str | None = None) -> int:
    work = workdir or tempfile.mkdtemp(prefix="northstar-")
    os.makedirs(work, exist_ok=True)
    src = os.path.join(work, "jpeg_tree")
    ds_dir = os.path.join(work, "dataset")
    ckpt = os.path.join(work, "ckpt")
    record: dict = {"workdir": work}

    # -- stage 1+2: JPEG tree → streaming import ---------------------------
    # The import runs in a CPU-pinned subprocess: this parent must stay
    # JAX-free so the chip is exclusively the training children's (a
    # chip belongs to one process at a time; the children below run one
    # after another).
    t0 = time.perf_counter()
    if not os.path.exists(os.path.join(ds_dir, "meta.json")):
        make_jpeg_tree(src)
        sys.path.insert(0, REPO)
        import reexec_cpu

        imp = subprocess.run(
            [
                sys.executable,
                "-c",
                "from mpit_tpu.data import import_image_directory; "
                f"import_image_directory({src!r}, {ds_dir!r}, size={STORE})",
            ],
            env=reexec_cpu.cpu_mesh_env(1),
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=600,
        )
        if imp.returncode != 0:
            print(imp.stdout[-2000:] + imp.stderr[-2000:])
            print("rehearsal: FAIL — import stage exited nonzero")
            return 1
    record["import_s"] = round(time.perf_counter() - t0, 1)
    print(f"rehearsal: imported {CLASSES}x{PER_CLASS} JPEGs -> {ds_dir} "
          f"({record['import_s']}s)")

    # -- stage 3+4: train on the chip, SIGTERM mid-run ---------------------
    env = dict(os.environ)
    proc = subprocess.Popen(
        _train_cmd(ds_dir, ckpt, 100000),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    time.sleep(150)  # compile (~1 min) + some steps
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=600)
    if proc.returncode != 0:
        print(out[-4000:])
        print("rehearsal: FAIL — preempted run exited nonzero")
        return 1
    res1 = _last_result(out)
    if not res1.get("preempted"):
        print(out[-4000:])
        print("rehearsal: FAIL — run was not preempted (SIGTERM too late?)")
        return 1
    record["preempted_at_step"] = res1["steps"]
    print(f"rehearsal: SIGTERM drained at step {res1['steps']}, "
          "checkpoint written")

    # -- stage 5: resume → finish → final padded val sweep -----------------
    # Target is relative to wherever the drain landed (the chip may run
    # hundreds of steps before the SIGTERM arrives).
    target = res1["steps"] + RESUME_STEPS
    t1 = time.perf_counter()
    proc2 = subprocess.run(
        _train_cmd(ds_dir, ckpt, target),
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900,
    )
    if proc2.returncode != 0:
        print((proc2.stdout + proc2.stderr)[-4000:])
        print("rehearsal: FAIL — resumed run exited nonzero")
        return 1
    res2 = _last_result(proc2.stdout)
    if res2.get("steps") != target or res2.get("preempted"):
        print(proc2.stdout[-4000:])
        print("rehearsal: FAIL — resume did not complete cleanly")
        return 1
    record["resume_wall_s"] = round(time.perf_counter() - t1, 1)
    record["final_loss"] = res2["final_loss"]
    record["eval"] = res2.get("eval", {})
    # Throughput through the REAL pipeline (mmap gather + C++ RRC +
    # train step), from the resumed run's logged rate.
    record["real_data_images_per_sec"] = res2.get("items_per_sec")
    print(f"rehearsal: resumed {record['preempted_at_step']}->{target}, "
          f"final val {record['eval']}")

    # -- stage 6: synthetic-stream control (input-pipeline overhead) -------
    proc3 = subprocess.run(
        [
            sys.executable, "-m", "mpit_tpu.asyncsgd", "imagenet",
            "--steps", str(RESUME_STEPS), "--batch-size", str(BATCH),
            "--image-size", str(TRAIN), "--lr", "0.005",
            "--log-every", "5",
        ],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900,
    )
    if proc3.returncode == 0:
        res3 = _last_result(proc3.stdout)
        if res3:
            record["synthetic_images_per_sec"] = res3.get("items_per_sec")
            real, synth = (
                record.get("real_data_images_per_sec"),
                record.get("synthetic_images_per_sec"),
            )
            if real and synth:
                record["input_pipeline_overhead_pct"] = round(
                    (1 - real / synth) * 100, 1
                )

    print("REHEARSAL " + json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else None))
