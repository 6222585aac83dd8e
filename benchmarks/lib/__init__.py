"""The benchmark's yardstick: what later PRs may add to and may not edit."""
