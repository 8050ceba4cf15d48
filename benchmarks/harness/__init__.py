"""The yardstick: cell lookup, the table of peaks, the trace reducer."""
