"""Layer "device": the backend's ``peak_bytes_in_use`` (live arrays; a
program's temporaries are under ``peak_bytes_reserved``, which is the
end-to-end ``peak_hbm_gb``). Source: the device's own counter."""


def read(obs):
    peak = obs["memory"]["peak_bytes_in_use"]
    return peak / 1e9 if peak else None
