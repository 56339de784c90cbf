"""Traffic generators: a traffic mix (benchmark/traffic/<name>.json) names one (``"generator"``) and it turns the parameters and --seed into inputs."""
"""Traffic generators: a traffic mix (benchmark/traffic/<name>.json) names one (``"generator"``) and it turns the parameters and --seed into inputs."""
