"""The table of peaks, keyed by ``device_kind``. A device that is not in
the table is an error, never a default."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind):
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            "device_kind %r is not in %s (known: %s): add its published "
            "peaks with their source before measuring on it"
            % (device_kind, _PATH,
               sorted(k for k in table if not k.startswith("_"))))
    return table[device_kind]
