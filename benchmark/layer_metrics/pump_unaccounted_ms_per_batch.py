"""What no span covers of a pump's cycle: mean `pump_cycle` less the ten
top-level spans that tile it (six check stages, take_wait, wire_decode,
serialize, send). The measurement's own blind spot."""
from istio_tpu.runtime import monitor

from spans import pump_unaccounted_ms


def begin(ctx):
    return monitor.stage_baseline()


def read(ctx, base):
    return pump_unaccounted_ms(base)
