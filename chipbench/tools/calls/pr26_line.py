"""Scratch (PR 26): a result line, or a file of them, in short: correct, the
device, every metric unrounded, the compared numbers and the idle gaps."""
import json
import sys

for path in sys.argv[1:]:
    for raw in open(path):
        if not raw.startswith("{"):
            continue
        d = json.loads(raw)
        d = d.get("line", d)
        dev = d["device"]
        print(f"-- {path}: correct {d['correct']} attempted {d['attempted']} "
              f"failed {d['failed']} device {dev['kind']} x{dev['count']} "
              f"busy_s {dev.get('busy_s')} window_s {dev.get('window_s')} "
              f"memory_peak_bytes {dev.get('memory_peak_bytes')}")
        for k, v in d["metrics"].items():
            print(f"   {k} = {v['value']!r} {v['unit']}")
        print("   compared", [(c["name"], c["value"], c["limit"])
                              for c in d["compared"]])
        if "breakdown" in d:
            print("   idle_gaps", d["breakdown"]["idle_gaps"])
            print("   device_ops", d["breakdown"]["device_ops"][:4])
