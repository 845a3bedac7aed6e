"""The yardstick's shared code: manifest, peaks, traffic generation, the trace
reduction, compile accounting and the comparison that decides `correct`."""
