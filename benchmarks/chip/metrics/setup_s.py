"""Process start until the window opens: device start, corpus and layout
built on the device, compiles or cache loads, warm-up of every shape."""


def read(rec):
    return rec["setup_s"], "s"
