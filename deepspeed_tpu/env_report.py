"""Environment / op-compatibility report (the ds_report CLI).

TPU-native equivalent of the reference env report (deepspeed/env_report.py:
op compatibility matrix + torch/cuda versions): reports jax/flax versions,
visible devices, the native toolchain, and for every registered op builder
whether its ops actually load — the honest version of the reference's
installed/compatible table.
"""

import shutil
import subprocess
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _version(mod_name):
    try:
        mod = __import__(mod_name)
        return getattr(mod, "__version__", "unknown")
    except Exception:
        return None


def op_compatibility():
    from .ops.op_builder import builder_names, get_builder_class
    rows = []
    for name in builder_names():
        cls = get_builder_class(name, backend="cpu")
        try:
            ok = cls().is_compatible(verbose=False)
        except Exception:
            ok = False
        rows.append((name, ok))
    return rows


def main():
    print("-" * 64)
    print("deepspeed_tpu environment report")
    print("-" * 64)
    print("software:")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint",
                "numpy", "ml_dtypes"):
        v = _version(mod.split(".")[0])
        print(f"  {mod:<18} {v if v else RED_NO}")
    import deepspeed_tpu
    print(f"  {'deepspeed_tpu':<18} {deepspeed_tpu.__version__}")

    print("native toolchain:")
    for tool in ("g++", "cmake", "ninja", "make"):
        path = shutil.which(tool)
        if path and tool == "g++":
            try:
                ver = subprocess.run([path, "--version"], capture_output=True,
                                     text=True, timeout=10
                                     ).stdout.splitlines()[0]
            except Exception:
                ver = path
            print(f"  {tool:<18} {ver}")
        else:
            print(f"  {tool:<18} {path or RED_NO}")

    print("devices:")
    # in this process: a chip belongs to one process at a time, and the op
    # table below needs the same backend
    import jax
    for d in jax.devices():
        print(f"  {d.id}: {d.device_kind} ({d.platform})")
    print(f"  process {jax.process_index()}/{jax.process_count()}")

    print("op compatibility:")
    for name, ok in op_compatibility():
        print(f"  {name:<22} {GREEN_OK if ok else RED_NO}")
    print("-" * 64)
    return 0


if __name__ == "__main__":
    sys.exit(main())
