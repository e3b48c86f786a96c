#!/usr/bin/env python
"""Where Tier-1's CPU-seconds go, from a junit file of the driver's
command (``--junitxml``, /root/TESTS_LAST_RUN.json): seconds a file, the
40 longest tests, the total and the total over the six workers.

    python scripts/tier1_times.py /tmp/_t1.xml
"""

import collections
import sys
import xml.etree.ElementTree as ET


def main(path: str) -> int:
    cases = [(float(c.get("time", 0)), c.get("classname", ""), c.get("name"))
             for c in ET.parse(path).getroot().iter("testcase")]
    files = collections.Counter()
    for seconds, module, _ in cases:
        files[module] += seconds
    total = sum(files.values())
    print(f"{len(cases)} tests, {total:.0f} CPU-seconds, "
          f"{total / 6:.0f} a worker of six")
    print("\nseconds a file (those of 20 s and more)")
    for module, seconds in files.most_common():
        if seconds >= 20:
            print(f"{seconds:8.0f}  {module}")
    print(f"{sum(s for s in files.values() if s < 20):8.0f}  "
          f"the other {sum(s < 20 for s in files.values())} files")
    print("\nthe 40 longest tests")
    for seconds, module, name in sorted(cases, reverse=True)[:40]:
        print(f"{seconds:8.1f}  {module}::{name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
