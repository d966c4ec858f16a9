def value(ev, spec):
    """Leaves of the hub's device state that are not on the chip (and 1
    more if it holds none at all)."""
    good, wrong = ev["device_leaves"]
    return float(wrong + (good == 0))
