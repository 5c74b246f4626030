"""Device: 1 - union of the device's operation intervals over the traced
slice, from the profiler trace. layer: device; moves submit_commit_p50_ms."""
from harness.scan import device_idle_pct as read  # noqa: F401
