"""The benchmark's own machinery: discovery, device, tracing, runners."""
