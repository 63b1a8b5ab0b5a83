"""The yardstick: loader, clocks, peaks, arrivals, trace reduction."""
