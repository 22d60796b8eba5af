"""paddle.version (the reference generates this at build time —
``python/setup.py.in`` writes full_version/major/minor/patch/rc and
cuda/cudnn probes; here the accelerator stack is XLA/PJRT)."""

from .. import __version__ as full_version

__all__ = ["full_version", "major", "minor", "patch", "rc", "show",
           "cuda", "cudnn", "istaged", "commit", "mkl", "tpu"]

_parts = full_version.split(".")
major = _parts[0]
minor = _parts[1] if len(_parts) > 1 else "0"
patch = _parts[2] if len(_parts) > 2 else "0"
rc = "0"
istaged = False
commit = "unknown"
with_gpu = "OFF"


def cuda():
    return False


def cudnn():
    return False


def mkl():
    return "OFF"


def tpu():
    """Non-reference probe: is a TPU-class device visible."""
    import jax
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except Exception:
        return False


def show():
    print(f"full_version: {full_version}")
    print(f"major: {major}\nminor: {minor}\npatch: {patch}\nrc: {rc}")
    print(f"commit: {commit}")
