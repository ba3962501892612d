"""``python -m sentinel_tpu.envoy_rls`` — standalone RLS token server.

Rules come from a JSON file (``SENTINEL_RLS_RULES`` or ``--rules``),
re-polled on mtime change so a ConfigMap update applies without restart::

    [{"domain": "web", "descriptors": [
        {"resources": [{"key": "path", "value": "/api"}], "count": 100}]}]
"""

from __future__ import annotations

import argparse
import json
import os
import time

from sentinel_tpu.envoy_rls.rule import (
    EnvoyRlsRule,
    EnvoyRlsRuleManager,
    KeyValueResource,
    ResourceDescriptor,
)
from sentinel_tpu.envoy_rls.service import SentinelEnvoyRlsService


def rules_from_json(text: str):
    out = []
    for d in json.loads(text or "[]"):
        out.append(EnvoyRlsRule(
            domain=d["domain"],
            descriptors=[
                ResourceDescriptor(
                    resources=[KeyValueResource(r["key"], r["value"])
                               for r in desc.get("resources", [])],
                    count=float(desc["count"]),
                )
                for desc in d.get("descriptors", [])
            ],
        ))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description="sentinel-tpu Envoy RLS server")
    ap.add_argument("--address",
                    default=os.environ.get("SENTINEL_RLS_ADDRESS",
                                           "0.0.0.0:10245"))
    ap.add_argument("--rules",
                    default=os.environ.get("SENTINEL_RLS_RULES", ""))
    args = ap.parse_args()
    from sentinel_tpu.utils import compile_cache

    compile_cache.enable()

    manager = EnvoyRlsRuleManager()
    service = SentinelEnvoyRlsService(manager)
    mtime = None
    if args.rules:
        with open(args.rules, "r", encoding="utf-8") as f:
            manager.load_rules(rules_from_json(f.read()))
        mtime = os.stat(args.rules).st_mtime
    server = service.serve_grpc(args.address)
    print(f"sentinel-tpu RLS serving on {args.address}", flush=True)
    try:
        while True:
            time.sleep(3)
            if not args.rules:
                continue
            try:
                m = os.stat(args.rules).st_mtime
            except OSError:
                continue
            if m != mtime:
                try:
                    with open(args.rules, "r", encoding="utf-8") as f:
                        manager.load_rules(rules_from_json(f.read()))
                    mtime = m  # recorded only on SUCCESS: a mid-write or
                    # malformed read retries next poll even when the final
                    # write lands in the same coarse mtime tick
                    print("RLS rules reloaded", flush=True)
                except (OSError, ValueError, KeyError, TypeError) as ex:
                    # Malformed/mid-write update: keep serving last-good.
                    print(f"RLS rules reload FAILED (kept last good): {ex!r}",
                          flush=True)
    except KeyboardInterrupt:
        server.stop(grace=1.0)


if __name__ == "__main__":
    main()
