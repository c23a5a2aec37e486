"""Shared artifact IO for the A/B measurement tools.

One invariant: an artifact holding KERNEL-side measurements is never
silently replaced by a run that has none — a sanity run on the wrong
host must not destroy evidence (r5 review findings). Such a run is still
recorded, in a sidecar next to the preserved original.
"""

import json


def _has_kernel_measurement(doc) -> bool:
    """True if any case row carries a numeric kernel-path timing."""
    for case in (doc or {}).get("cases", []):
        for k, v in case.items():
            if k in ("pallas_ms", "flash_ms") and isinstance(v, (int, float)):
                return True
    return False


def _case_key(case: dict):
    return case.get("case") or case.get("T")


def _kernel_timings(case: dict) -> dict:
    return {k: v for k, v in case.items()
            if k in ("pallas_ms", "flash_ms") and isinstance(v, (int, float))}


def write_unless_clobbering(path: str, out: dict) -> None:
    try:
        with open(path) as f:
            existing = json.load(f)
    except (OSError, ValueError):
        existing = None
    if _has_kernel_measurement(existing) and not _has_kernel_measurement(out):
        side = path.replace(".json", ".degraded.json")
        with open(side, "w") as f:
            json.dump(out, f, indent=1)
        print("kernel-measured artifact preserved at", path,
              "- degraded run recorded at", side, flush=True)
        return
    if existing:
        # partially-degraded run: for any case the old artifact measured on
        # the kernel path but this run only errored, carry the prior
        # measurement along instead of silently deleting it
        old_by_key = {_case_key(c): c for c in existing.get("cases", [])
                      if isinstance(c, dict)}
        for case in out.get("cases", []):
            old = old_by_key.get(_case_key(case))
            if old and _kernel_timings(old) and not _kernel_timings(case):
                case["prior_kernel_measurement"] = {
                    **_kernel_timings(old),
                    "from_device": existing.get("device", "?")}
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print("wrote", path, flush=True)

