"""The discrete-event scheduler simulation: Megha, its baselines, the event
loop and the delay metrics."""
