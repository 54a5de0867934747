"""Layer ingest: seconds of ``SlotDataset.load_into_memory`` (read and
parse the pass's slot-text files), mean over the measured passes. Source:
the harness's span around the call."""


def read(record):
    passes = record["passes"]
    return sum(p["ingest_s"] for p in passes) / len(passes) if passes \
        else None
