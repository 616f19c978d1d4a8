"""setup_s (host clock): process start to the first measured step: device
start, weights made on the device, compile or cache load, checked steps."""


def read(rec):
    return rec["setup_s"]
