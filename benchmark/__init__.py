"""The chip benchmark's harness, configurations, traffic and metric readers."""
