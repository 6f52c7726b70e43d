# Root conftest: run the suite on a virtual 8-device CPU mesh, chosen BEFORE
# any test imports jax. Mirrors the reference's CI strategy of substituting
# real services with local stand-ins (reference .github/workflows/go.yml:61-91
# runs Kafka/Redis/MySQL containers; our "service container" is the CPU PJRT
# backend). A developer who sets JAX_PLATFORMS explicitly keeps their choice.
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
if not os.environ.get("JAX_PLATFORMS"):
    os.environ["JAX_PLATFORMS"] = "cpu"

# One persistent XLA compilation cache for the whole suite (the tier-1 run
# is dominated by compiles of tiny test models that every engine test
# re-builds), through the same helper the engine calls: the directory
# JAX_COMPILATION_CACHE_DIR names, else <checkout>/.xla_cache. Configured
# HERE — before any test compiles — so non-engine tests share it too.
from gofr_tpu.utils import enable_compilation_cache  # noqa: E402

enable_compilation_cache()
