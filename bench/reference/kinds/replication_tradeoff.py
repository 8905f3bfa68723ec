"""Configuration rows of a ``replication_tradeoff`` request: failover
mode x checkpoint interval x brownout ramp, in that nesting order. An
interval of None means no checkpoints; a brownout row's ramps are added
to the chaos spec's own."""
from bench.reference.model import CheckpointConfig


def rows(args: dict) -> list[dict]:
    upload = args.get("ckpt_upload_s", 4.0)
    out = []
    for fo in args["failovers"].values():
        for iv in args.get("ckpt_intervals", (None, 10.0, 30.0)):
            for bro in args.get("brownouts", ((), ((0.0, 1e9, 4.0),))):
                out.append({"failover": fo,
                            "ckpt": (None if iv is None else
                                     CheckpointConfig(interval_s=iv,
                                                      upload_s=upload)),
                            "brownout": tuple(bro), "upgrade": None})
    return out
