"""The chip benchmark of the temporal-graph serving path (``bench/run.py``)."""
import os


def prepare(root: str) -> None:
    """Before JAX loads: its compilation cache in a fixed directory inside
    the checkout, kept whole (no size limit, so no eviction), and the TPU
    runtime's logs inside the checkout too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    logs = os.environ.setdefault(
        "TPU_LOG_DIR", os.path.join(root, ".bench_trace", "tpu_logs"))
    os.makedirs(logs, exist_ok=True)
