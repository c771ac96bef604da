"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of device op intervals."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1 - tr["busy_s"] / tr["window_s"]
