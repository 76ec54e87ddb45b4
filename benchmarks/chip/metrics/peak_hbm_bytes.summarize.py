"""Peak bytes in use on the chip (`memory_stats()["peak_bytes_in_use"]`),
read after the window and before the reference runs."""


def read(obs):
    return obs.get("peak_hbm_bytes")
