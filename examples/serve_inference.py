"""Single-chip serving: load a model, classify images, print top-5.

    python examples/serve_inference.py --model ResNet50 img1.jpeg img2.jpeg

Equivalent to the reference's `predict-locally` CLI verb
(reference worker.py:1891-1925), on the TPU engine.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import json


def main() -> None:
    from dml_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="ResNet50")
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("files", nargs="+", help="image files (jpeg)")
    args = p.parse_args()

    from dml_tpu.inference.engine import InferenceEngine

    engine = InferenceEngine()
    engine.load_model(args.model, batch_size=args.batch_size)
    result = engine.infer_files(args.model, args.files)
    print(json.dumps(result.to_json_dict(), indent=2))
    print(f"# decode {result.load_time:.3f}s  device {result.infer_time:.3f}s")


if __name__ == "__main__":
    main()
