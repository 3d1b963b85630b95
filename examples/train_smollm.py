"""End-to-end driver: train the full 135M smollm-135m for a few hundred
steps on the synthetic token pipeline, with checkpointing + fault tolerance.

    PYTHONPATH=src python examples/train_smollm.py --steps 300

This is a thin preset around repro.launch.train (the production driver);
on a TPU pod the same command with --mesh production shards over
(data=16, model=16).  On this CPU container a full-135M step at seq 256 is
~10 s; pass --steps 30 for a quick demonstration (loss drops from ~10.8
toward the n-gram entropy of the synthetic stream).
"""
import sys

from repro.launch.train import main

if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    preset = [
        "--arch", "smollm-135m",
        "--steps", "300",
        "--batch", "4",
        "--seq", "256",
        "--lr", "1e-3",
        "--ckpt-dir", "/tmp/smollm_ckpt",
        "--ckpt-every", "20",
        "--log-every", "5",
    ]
    # user args override the preset (argparse last-wins)
    sys.argv = [sys.argv[0]] + preset + sys.argv[1:]
    main()
