"""The on-chip benchmark's own machinery: registry, device, traffic,
trace reduction, and the serving and training loops."""
