def device_bytes_limit() -> int:
    """Bytes of memory the first local device reports as its limit; 0 on
    the CPU backend, which reports none (callers keep their host defaults).
    On the TPU backend a device that reports no ``bytes_limit`` is an
    error: a guessed budget would hide that the engine cannot see its chip."""
    import jax

    dev = jax.local_devices()[0]
    total = int((dev.memory_stats() or {}).get("bytes_limit", 0))
    if not total and dev.platform == "tpu":
        raise RuntimeError(
            f"{dev} reports no bytes_limit; cannot size device memory budgets"
        )
    return total
