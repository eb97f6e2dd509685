"""1 - union of device-operation intervals over the traced window, mean
over the chips used."""


def read(ev):
    if not ev.trace or ev.trace["idle_share"] is None:
        return None
    return 100.0 * ev.trace["idle_share"]
