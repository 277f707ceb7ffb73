"""Seconds from spawning the server until /minio/health/live answers:
interpreter and imports, host self-test, JAX init, device self-test and
warm-up (admin info `erasure.boot` holds the last three)."""


def read(ctx: dict) -> float | None:
    return ctx.get("boot_s")
