"""Batched serving example (deliverable b): prefill + decode with KV /
SSM / xLSTM caches across architectures.

    PYTHONPATH=src python examples/serve_batch.py --arch xlstm-125m
"""
import argparse

from repro.launch.serve import main as serve_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    serve_main(["--arch", args.arch, "--smoke", "--slots",
                str(args.batch), "--prompt-len-range", "16", "16",
                "--gen-range", str(args.gen), str(args.gen),
                "--temperature", "0.8"])


if __name__ == "__main__":
    main()
