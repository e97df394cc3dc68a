#!/usr/bin/env python
"""Run the imported-BERT fine-tune benchmark (BASELINE config 4) on the
real chip and record the artifact as FINETUNE_r05.json — >=40% MFU with
flash verifiably in the hot path AND (r5) a held-out accuracy
trajectory on the real hand-written sentiment corpus (round-4 review item
3: quality evidence, not random-token memorization)."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench  # noqa: E402


def main():
    bench.enable_compile_cache()
    r = bench.bench_bert_imported()
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "FINETUNE_r05.json")
    with open(out, "w") as f:
        json.dump(r, f, indent=1)
    print(json.dumps(r))


if __name__ == "__main__":
    main()
