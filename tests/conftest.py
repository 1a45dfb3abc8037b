import os

# smoke tests and benches must see ONE device (the dry-run sets its own
# 512-device flag in-process); keep any user XLA_FLAGS out of the way
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from hypothesis import HealthCheck, settings

settings.register_profile(
    "ci", max_examples=25, deadline=None,
    suppress_health_check=[HealthCheck.too_slow])
# the nightly chaos leg (.github/workflows/ci.yml) runs the randomized
# differential suites with a date-derived --hypothesis-seed and a deeper
# example budget; select it with HYPOTHESIS_PROFILE=chaos
settings.register_profile(
    "chaos", max_examples=200, deadline=None,
    suppress_health_check=[HealthCheck.too_slow])
settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
