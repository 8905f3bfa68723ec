"""Configuration rows of a ``deployment_drill`` request: upgrade policy
x canary fraction x rollback threshold, in that nesting order, each row
sharing the request's failover and checkpoint configs."""
import dataclasses
import math

from bench.reference.model import FailoverConfig


def rows(args: dict) -> list[dict]:
    out = []
    for pol in args["policies"].values():
        for frac in args.get("canary_fracs", (0.25, 0.5)):
            for thr in args.get("rollback_thresholds", (math.inf, 200.0)):
                out.append({
                    "failover": args.get("failover") or FailoverConfig(),
                    "ckpt": args.get("ckpt"), "brownout": (),
                    "upgrade": dataclasses.replace(
                        pol, canary_frac=float(frac),
                        rollback_threshold=float(thr))})
    return out
