"""Layer device: what the runtime kept reserved, at its peak, beside the
buffers ``peak_hbm_gb`` counts — on a TPU the temporaries of the largest
compiled program (the AOT compile's ``temp_size_in_bytes``). Source:
``memory_stats()["peak_bytes_reserved"]`` of the fullest chip."""


def read(record):
    reserved = record["memory"].get("peak_bytes_reserved")
    return None if not reserved else reserved / 1e9
