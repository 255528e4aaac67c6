"""End-to-end LM training with quantized gradient exchange.

Trains a ~15M-param tinyllama-family model for a few hundred steps on the
deterministic synthetic pipeline across 8 forced host devices, with the
paper's compressed data-parallel exchange (two-phase int8), and verifies
the loss trajectory matches full-precision training.

Run: PYTHONPATH=src python examples/train_lm.py [--steps 200]
(thin wrapper over repro.launch.train — the production driver — called
in this process, which then owns whatever device it trains on)
"""

import argparse

from repro.launch import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--compression", default="int8", choices=("none", "int8", "int4"))
    ap.add_argument("--compressor", default="qgenx",
                    choices=("qgenx", "randk", "layerwise", "none"))
    ap.add_argument("--level-schedule", default="fixed", choices=("fixed", "qada"))
    args = ap.parse_args()
    cmd = [
        "--arch", "tinyllama-1.1b", "--reduced",
        "--host-devices", "8",
        "--steps", str(args.steps),
        "--batch", "16", "--seq", "128",
        "--compression", args.compression,
        "--compressor", args.compressor,
        "--compress-axis", "data",
        "--level-schedule", args.level_schedule,
        "--optimizer", "extra_adam",
        "--log-every", "10",
    ]
    if args.level_schedule == "qada":
        cmd += ["--level-update-every", "10"]
    print("+ repro.launch.train", " ".join(cmd))
    train.main(cmd)


if __name__ == "__main__":
    main()
