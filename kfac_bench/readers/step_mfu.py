"""The whole step's share of the chip's peak: model FLOPs of a step
(forward and backward, from the configuration's sizes; K-FAC's own work
does not count) times steps over the window, over chips times the
``device_kind``'s bf16 peak, in percent."""


def read(run):
    if not run['steps'] or run['peaks'] is None:
        return None
    rate = run['flops_per_step'] * run['steps'] / run['window_s']
    return 100.0 * rate / (run['chips'] * run['peaks']['bf16_flops'])
