"""The device's ``peak_bytes_in_use`` after the window, in GiB."""


def read(data):
    peak = data.device.get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
