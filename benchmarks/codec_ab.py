#!/usr/bin/env python
"""Wire-codec convergence A/B: fp32 against int8 with and without error
feedback, two ranks through the launcher on the CPU. Run by
``benchmarks/engine_scaling.py --codec`` (``ci.sh --codec``), whose
``--check`` gates on what it prints; alone:

    python benchmarks/codec_ab.py [--steps 150] [--out ab.json]

Worker mode is selected internally via HVT_BENCH_CODEC_AB.
"""

import json
import os
import sys

# ---------------------------------------------------------------------------
# wire-codec convergence A/B
# ---------------------------------------------------------------------------
#
# Small REAL-training probe for the quantized wire codecs: 2 ranks run
# SGD on a least-squares problem whose gradient buffer carries a large
# constant "loss-scale" slot in its first 256-elem block — the fused-
# buffer shape real jobs put on the wire (tensor fusion mixes tensors
# of wildly different magnitudes into shared quantization blocks). That
# slot pins the block's absmax at every quantization stage (per-rank
# send, per-hop partial sums, the owner's roundtrip), so the true
# gradient components sharing its block sit permanently below the int8
# threshold: without error feedback they are zeroed EVERY step and
# their weights never train; the residual carry recovers them. The
# second block has no such slot and is the in-test control. Three
# configs share the identical deterministic problem: fp32 (codec off),
# int8+EF, int8−EF. The committed acceptance
# (benchmarks/r09_codec_sweep.json --check): int8+EF's final loss
# within noise of fp32, int8−EF measurably biased.


def _codec_ab_worker():
    import numpy as np

    import horovod_tpu as hvt

    hvt.init()
    r = hvt.rank()
    steps = int(os.environ.get("HVT_BENCH_AB_STEPS", "150"))
    d = 512                                  # 2 quantization blocks
    w_true = np.full(d, 0.15, np.float32)    # below the pinned threshold
    rng = np.random.RandomState(1000 + r)
    y = (w_true + rng.randn(d).astype(np.float32) * 0.01)  # rank's data
    w = np.zeros(d, np.float32)
    lr = 0.1
    aux = 100.0  # fused telemetry slot: pins block 0's absmax
    for _ in range(steps):
        g_local = (w - y).astype(np.float32)
        buf = np.concatenate(([np.float32(aux)], g_local))
        out = np.asarray(hvt.allreduce(buf, op=hvt.Average, name="grad"))
        g = out[1:]
        w = (w - lr * g).astype(np.float32)
    local_loss = 0.5 * float(np.mean((w - y) ** 2))
    losses = np.asarray(hvt.allgather(
        np.array([local_loss], np.float64), name="ab_loss"))
    if r == 0:
        print("HVT_AB_RESULT " + json.dumps(
            {"final_loss": float(losses.mean()),
             "pinned_block_coord": float(w[0]),
             "control_block_coord": float(w[400]),
             "steps": steps}), flush=True)
    hvt.shutdown()


def codec_ab_main(argv):
    """Drive the three-config A/B; prints one JSON line and optionally
    writes it (`--out`). CPU-only, ~seconds per config."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def argval(flag, dflt):
        return argv[argv.index(flag) + 1] if flag in argv else dflt

    steps = argval("--steps", "150")
    out_path = argval("--out", "")
    configs = {
        "fp32": {},
        "int8_ef": {"HVT_WIRE_COMPRESSION": "int8",
                    "HVT_ERROR_FEEDBACK": "1"},
        "int8_noef": {"HVT_WIRE_COMPRESSION": "int8",
                      "HVT_ERROR_FEEDBACK": "0"},
    }
    record = {"harness": "codec_ab r1", "steps": int(steps),
              "configs": {}}
    for name, extra in configs.items():
        env = dict(os.environ)
        # the fp32 reference must actually be fp32: an ambient
        # HVT_WIRE_COMPRESSION / HVT_ERROR_FEEDBACK in the caller's
        # shell would leak into the baseline arm and collapse the A/B
        # deltas toward zero
        env.pop("HVT_WIRE_COMPRESSION", None)
        env.pop("HVT_ERROR_FEEDBACK", None)
        env.update({"HVT_BENCH_CODEC_AB": "1",
                    "HVT_BENCH_AB_STEPS": steps,
                    "HVT_SHM_ALLREDUCE": "0",  # the wire is under test
                    "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
                    "PYTHONPATH": repo + os.pathsep
                    + env.get("PYTHONPATH", "") if env.get("PYTHONPATH")
                    else repo})
        env.update(extra)
        proc = subprocess.run(
            [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
             "2", sys.executable, os.path.abspath(__file__)],
            env=env, cwd=repo, capture_output=True, text=True,
            timeout=600)
        if proc.returncode != 0:
            raise RuntimeError(f"codec-ab config {name} failed:\n"
                               f"{proc.stdout}\n{proc.stderr}")
        for line in proc.stdout.splitlines():
            if "HVT_AB_RESULT" in line:
                record["configs"][name] = json.loads(
                    line.split("HVT_AB_RESULT ", 1)[1])
                break
        else:
            raise RuntimeError(f"no result line for {name}:\n"
                               f"{proc.stdout}")
        print(f"codec-ab {name}: "
              f"{record['configs'][name]['final_loss']:.6f}", flush=True)
    base = record["configs"]["fp32"]["final_loss"]
    record["delta_int8_ef"] = record["configs"]["int8_ef"][
        "final_loss"] - base
    record["delta_int8_noef"] = record["configs"]["int8_noef"][
        "final_loss"] - base
    print(json.dumps(record))
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
    return record


if __name__ == "__main__":
    if os.environ.get("HVT_BENCH_CODEC_AB"):
        _codec_ab_worker()
    else:
        codec_ab_main(sys.argv)
