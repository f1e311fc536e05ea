"""Privacy attack demo (Table VI threat model): a semi-honest edge server
tries to reconstruct client activations and identify input tokens from
the uplink payload, under four protection levels.

  PYTHONPATH=src python examples/privacy_attack_demo.py
"""
import sys

sys.path.insert(0, ".")

from benchmarks.bench_privacy import run  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


if __name__ == "__main__":
    enable_compile_cache()
    print("protection            rho    cos     mse     token-id acc")
    rows = run()
    for name, rho, cos, mse, acc in rows:
        print(f"{name:20s} {rho:>5s} {cos:7.4f} {mse:7.3f} {acc:7.4f}")
    print("\nELSA (SS-OP + sketch) lowers reconstruction cosine and token")
    print("identification below sketch-only at every rho; r=16 > r=8.")
